"""AKG bridge: tensor ops → SCoPs → PolyTOPS schedule trees → kernel plans.

This is how the paper's scheduler becomes a first-class feature of the
TPU framework (DESIGN.md §2): the schedule tree produced by PolyTOPS for
an operator's SCoP (:mod:`repro.core.schedtree` — the same IR the numpy
and C emitters walk) is *lowered* into a :class:`KernelPlan` — grid
dimension order from the outer bands, the lane-mapped vector dim from
the ``vector`` mark (or the vectorize directive / innermost band),
BlockSpec tile shapes fitted to VMEM via the shared cache model —
consumed by the Pallas kernels in ``repro.kernels``.

:func:`lower_to_kernel_plan` is fully general: any scheduled SCoP's tree
maps to a plan.  ``plan_matmul`` / ``plan_attention`` /
``plan_mamba_scan`` are thin wrappers that build the operator SCoP,
schedule it (through the structural schedule cache, tree included in the
payload) and lower — plus at most a kernel-specific tile fit (flash
attention's online-softmax state, the mamba VMEM-resident hidden state,
the matmul's HBM traffic and grid steps).

TPU adaptation: the vector iterator maps to the 128-lane VPU axis, the
next-inner to 8 sublanes; tiles snap to LANE/SUBLANE multiples; tile
sizes are chosen so the working set — from the statement's *real* access
groups (:func:`repro.core.cachemodel.stmt_access_groups`), times the
double/triple-buffering factor — fits VMEM (~16 MiB usable).  The SSM
kernels' blocks are not the statement's accesses (another layout, vreg
padding of the state dim), so their plans are fitted to the kernels'
real blocks instead (:func:`scan_block_bytes`).  This replaces the
paper's externally-provided NPU tile sizes.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import Callable, Dict, List, Optional, Tuple

from ..launch.roofline import HBM_BW, PEAK_FLOPS
from .config import tensor_style
from .resilience import provenance as _provenance, schedule_with_ladder
from .schedcache import global_cache
from .schedtree import ScheduleTree, schedule_tree, yvar
from .scop import Scop, Statement

VMEM_BYTES = 16 * 2**20
LANE = 128
SUBLANE = 8
#: the vector register file: 64 vregs of one (SUBLANE, LANE) 32-bit tile
VREG_FILE_BYTES = 64 * SUBLANE * LANE * 4


@dataclass(frozen=True)
class KernelPlan:
    """Loop-nest plan for a Pallas kernel.

    ``degraded``/``fallback_level``/``degrade_reasons`` carry the
    degradation-ladder provenance of the schedule the plan was lowered
    from (see :mod:`repro.core.resilience`): a plan is still *correct*
    when degraded — every ladder rung is legal — but it may be lowered
    from a fallback schedule rather than the configured one, which a
    serving layer may want to log or re-plan later."""
    loop_order: Tuple[str, ...]       # outer → inner iterator names
    vector_iter: Optional[str]        # lane-mapped innermost iterator
    tile: Dict[str, int]              # iterator -> tile size
    bands: Tuple[int, ...]            # band id per scheduled dim
    schedule_str: str = ""            # human-readable schedule (debug)
    degraded: bool = False
    fallback_level: int = 0
    degrade_reasons: Tuple[str, ...] = ()


def _matmul_scop(m: int, n: int, k: int) -> Scop:
    s = Scop("pallas_matmul", params={"M": m, "N": n, "K": k})
    with s.loop("i", 0, "M"):
        with s.loop("j", 0, "N"):
            with s.loop("kk", 0, "K"):
                s.stmt("C[i,j] = C[i,j] + A[i,kk] * B[kk,j]")
    return s


def _iter_extents(scop: Scop, stmt: Statement) -> Dict[str, int]:
    """Concrete trip count per statement iterator (parameter values baked
    in) — the dimension sizes the VMEM tile fitter works against."""
    from .cachemodel import stmt_iter_ranges

    return {it: (max(1, int(rng[1] - rng[0]) + 1) if rng is not None else 1)
            for it, rng in stmt_iter_ranges(scop, stmt).items()}


def _fit_tiles(order: List[str], dims: Dict[str, int], vector_iter: str,
               stmt: Statement,
               fixed: Optional[Dict[str, int]] = None,
               block_bytes: Optional[Callable[[Dict[str, int]], int]] = None,
               floor: Optional[Dict[str, int]] = None,
               start: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """Snap tiles to TPU-friendly sizes under a VMEM budget.

    By default the working set comes from the shared cache model
    (:func:`repro.core.cachemodel.stmt_access_groups`): per-access tile
    footprints from the statement's actual subscript strides, in bf16,
    times 3 for triple buffering — the same estimator that sizes CPU
    cache tiles sizes VMEM tiles.  A kernel whose blocks differ
    from the statement's accesses (other operands, another layout, vreg
    padding) passes ``block_bytes``: its real VMEM bytes for a tile,
    which then replaces the estimate.

    ``fixed`` pins dims to a given tile (e.g. a VMEM-resident state dim
    that must stay whole); pinned dims are exempt from shrinking, so the
    others shrink against the true footprint.  ``floor`` gives the
    smallest tile a dim may shrink to (default ``SUBLANE``); ``start``
    the tile a dim starts from before it shrinks."""
    from .cachemodel import stmt_access_groups, working_set_bytes

    fixed = fixed or {}
    start = start or {}
    tile = {}
    for it in order:
        d = dims[it]
        if it in fixed:
            tile[it] = min(fixed[it], d)
        elif it in start:
            tile[it] = min(start[it], d)
        elif it == vector_iter:
            tile[it] = min(d, 512 if d % 512 == 0 else LANE * max(d // LANE, 1))
            tile[it] = max(min(tile[it], d), min(d, LANE))
        else:
            tile[it] = min(d, 128 if d >= 128 else d)
    if block_bytes is None:
        groups = stmt_access_groups(stmt, order)

        def block_bytes(tile):
            sizes = [tile[i] for i in order]
            return 3 * working_set_bytes(groups, sizes, 2)
    low = {it: min(SUBLANE, dims[it]) for it in order}
    low.update({it: min(v, dims[it]) for it, v in (floor or {}).items()})

    # shrink until the working set fits VMEM
    shrink_order = [it for it in order if it != vector_iter and it not in fixed]
    while block_bytes(tile) > VMEM_BYTES and any(tile[i] > low[i]
                                                 for i in shrink_order):
        for it in shrink_order:
            if tile[it] > low[it]:
                tile[it] //= 2
                break
    return tile


def scan_block_bytes(tile: Dict[str, int], fused: bool) -> int:
    """VMEM bytes the SSM kernels (:mod:`repro.kernels.mamba_scan`,
    :mod:`repro.kernels.scan_gate` when ``fused``) hold for a (t, d, n)
    tile: every operand counted as f32, each block's two minor dims
    padded to the (8, 128) vreg tile, pipelined blocks double-buffered,
    plus the state scratch.  Mirrors the kernels' BlockSpecs: unfused,
    a/b (t, n, d), c (t, n, 1) and the y rows (t, d); fused, the Δ, x, z
    and o rows (t, d), B and C (t, n, 1), A, h0 and h_out (n, d) and the
    (1, d) skip."""
    t, d, n = tile["t"], tile["d"], tile["n"]

    def blk(rows: int, cols: int, lead: int = 1) -> int:
        return (lead * -(-rows // SUBLANE) * SUBLANE
                * -(-cols // LANE) * LANE * 4)

    if fused:
        blocks = 4 * blk(t, d) + 2 * blk(n, 1, t) + 3 * blk(n, d) + blk(1, d)
    else:
        blocks = 2 * blk(n, d, t) + blk(n, 1, t) + blk(t, d)
    return 2 * blocks + blk(n, d)


def lower_to_kernel_plan(tree: ScheduleTree, stmt_idx: Optional[int] = None,
                         *, sched=None) -> KernelPlan:
    """Map any scheduled SCoP's schedule tree to a :class:`KernelPlan`.

    * **grid order** — outer→inner point bands of the tree (tile/wave
      counter bands are post-processing artifacts and skipped), each
      mapped back to the statement iterator it scans through the tree's
      iterator substitution;
    * **vector dim** — the band carrying the ``vector`` mark when one
      exists, else the schedule's vectorize directive, else the
      innermost loop (contiguity put it there);
    * **tiles** — lane/sublane-snapped sizes fitted to VMEM via the
      shared cache model (:func:`_fit_tiles`).

    ``stmt_idx`` defaults to the deepest statement (scalar-init
    statements have no loop nest to map to a grid); a zero-dimensional
    choice raises ``ValueError`` so rankers can drop the candidate.

    ``sched`` (the Schedule the tree was built from) supplies the
    degradation-ladder provenance stamped on the plan; omitted, the
    plan reports a clean, non-degraded lowering.
    """
    scop = tree.scop
    if stmt_idx is None:
        stmt_idx = max(range(len(scop.statements)),
                       key=lambda i: (scop.statements[i].dim, -i))
    stmt = scop.statements[stmt_idx]
    if stmt.dim == 0:
        raise ValueError(
            f"statement S{stmt.index} has no loop dimensions to lower")
    sub = tree.subst.get(stmt.index, {})
    order: List[str] = []
    vec: Optional[str] = None
    for band in tree.bands():
        if stmt.index not in band.stmts or band.role:
            continue
        y = yvar(band.dim)
        cands = [it for it in stmt.iters if sub.get(it, {}).get(y)]
        if len(cands) == 1 and cands[0] not in order:
            order.append(cands[0])
            if band.vector and vec is None:
                vec = cands[0]
    for it in stmt.iters:     # safety: append anything unplaced
        if it not in order:
            order.append(it)
    if vec is None:
        vi = tree.vector_iter.get(stmt.index)
        vec = stmt.iters[vi] if vi is not None else order[-1]
    dims = _iter_extents(scop, stmt)
    tile = _fit_tiles(order, dims, vec, stmt)
    prov = _provenance(sched) if sched is not None else None
    return KernelPlan(tuple(order), vec, tile, tuple(tree.sched_bands),
                      tree.pretty,
                      degraded=bool(prov["degraded"]) if prov else False,
                      fallback_level=prov["fallback_level"] if prov else 0,
                      degrade_reasons=tuple(prov["reasons"]) if prov else ())


def _remote_plan(kind: str, *args, **kwargs) -> Optional[KernelPlan]:
    """Route a kernel plan through a running schedd daemon, if any.

    Returns None (plan locally) unless ``POLYTOPS_SCHEDD_SOCK`` points
    at a live daemon — and never from inside the daemon itself or a
    client's fallback path (:mod:`schedclient` guards both).  Remote
    failures of any kind also return None: the daemon is an amortizer,
    never a point of failure for planning."""
    from .schedclient import maybe_remote_plan

    plan = maybe_remote_plan(kind, *args, **kwargs)
    return plan if isinstance(plan, KernelPlan) else None


#: default in-process memo capacity per planner.  Ragged serving shapes
#: produce one (seq_q, seq_k, head_dim) triple per distinct chunk×page
#: geometry, so attention needs far more than the historical 8 entries
#: (which thrashed: every continuous-batching tick re-planned).
#: Override per planner with ``POLYTOPS_PLAN_MEMO_<NAME>`` or globally
#: with ``POLYTOPS_PLAN_MEMO``.
PLAN_MEMO_DEFAULTS: Dict[str, int] = {
    "matmul": 64, "attention": 64, "mamba_scan": 16, "scan_gate": 16,
}


def plan_memo_size(name: str) -> int:
    """Resolved memo capacity for planner ``name`` (env-overridable)."""
    import os
    raw = (os.environ.get(f"POLYTOPS_PLAN_MEMO_{name.upper()}")
           or os.environ.get("POLYTOPS_PLAN_MEMO"))
    if raw:
        try:
            return max(1, int(raw))
        except ValueError:
            pass
    return PLAN_MEMO_DEFAULTS.get(name, 16)


def _plan_memo(name: str):
    """Like ``functools.lru_cache`` but degraded plans are returned
    without being pinned: a plan lowered from a fault- or deadline-
    degraded schedule must not be served for the rest of the process —
    the next call re-plans and caches the clean result once the
    transient clears (the in-memory twin of schedcache's rule that
    degraded schedules are never published).

    Capacity is resolved per call via :func:`plan_memo_size`, so a
    serving process can widen a thrashing memo with one env var."""
    def deco(fn):
        memo: Dict[tuple, KernelPlan] = {}

        @functools.wraps(fn)
        def wrapper(*args):
            hit = memo.get(args)
            if hit is not None:
                return hit
            plan = fn(*args)
            if not plan.degraded:
                while len(memo) >= plan_memo_size(name):  # FIFO, as lru
                    memo.pop(next(iter(memo)))
                memo[args] = plan
            return plan

        wrapper.cache_clear = memo.clear
        return wrapper
    return deco


@_plan_memo("matmul")
def plan_matmul(m: int, n: int, k: int,
                strategy: str = "tensor") -> KernelPlan:
    """PolyTOPS-planned matmul: tensor-style scheduling yields the
    cache/VMEM-friendly (i, k, j) order with j vectorized (lanes); the
    tiles are then fitted to the kernel (:func:`_fit_matmul_plan`)."""
    remote = _remote_plan("matmul", m, n, k, strategy)
    if remote is not None:
        return remote
    return _fit_matmul_plan(lower_matmul(m, n, k), m, n, k)


def lower_matmul(m: int, n: int, k: int) -> KernelPlan:
    """The general lowering of the matmul SCoP's schedule tree, with
    :func:`_fit_tiles`'s tiles."""
    scop = _matmul_scop(m, n, k)
    cfg = tensor_style()
    cfg.auto_vectorize = True
    # structural cache: repeat plans for the same (m, n, k) shape are a
    # lookup, persisted on disk across serving/benchmark processes —
    # with the schedule tree riding along in the payload.  The ladder
    # makes planning total: a fault degrades the schedule (provenance on
    # the plan) instead of failing the kernel build.
    sched = schedule_with_ladder(scop, cfg, cache=global_cache(),
                                 with_tree=True)
    return lower_to_kernel_plan(schedule_tree(sched), sched=sched)


def _tile_choices(d: int, unit: int) -> List[int]:
    """Tiles a dim of extent ``d`` may take: its divisors that are
    multiples of ``unit``, and the whole dim."""
    return [t for t in range(unit, d, unit) if d % t == 0] + [d]


def matmul_block_bytes(tile: Dict[str, int], elem_bytes: int = 4) -> int:
    """VMEM bytes the planned matmul kernel holds for a tile: its A, B
    and C blocks double-buffered, plus the f32 accumulator.  Counted at
    f32 operands by default, so a tile that fits holds for bf16 and f32
    callers alike; that bound is tighter than :func:`_fit_tiles`'s
    (3 × the bf16 footprint of the statement's accesses)."""
    bm, bn, bk = tile["i"], tile["j"], tile["kk"]
    return 2 * elem_bytes * (bm * bk + bk * bn + bm * bn) + 4 * bm * bn


def matmul_tiles(m: int, n: int, k: int) -> List[Dict[str, int]]:
    """The tiles the matmul fit chooses among: lane-snapped j and kk,
    sublane-snapped i, each dividing its dim, whose blocks
    (:func:`matmul_block_bytes`) fit VMEM."""
    tiles = ({"i": bm, "j": bn, "kk": bk}
             for bm in _tile_choices(m, SUBLANE)
             for bn in _tile_choices(n, LANE)
             for bk in _tile_choices(k, LANE))
    return [t for t in tiles if matmul_block_bytes(t) <= VMEM_BYTES]


def matmul_hbm_bytes(m: int, n: int, k: int, tile: Dict[str, int],
                     elem_bytes: int = 2) -> int:
    """HBM bytes the planned matmul kernel (:mod:`repro.kernels.
    matmul_polytops`) moves at ``tile``, counted the way its BlockSpecs
    fetch: Pallas skips a block's copy while its index is unchanged, so
    the (bm, K) A panel stays resident when ``bk == K`` and is otherwise
    read once per j tile; the weight is read once per i tile; C is
    written once."""
    bm, bn, bk = tile["i"], tile["j"], tile["kk"]
    a = m * k * (1 if bk == k else n // bn)
    return elem_bytes * (a + k * n * (m // bm) + m * n)


def matmul_grid_steps(m: int, n: int, k: int, tile: Dict[str, int]) -> int:
    return (m // tile["i"]) * (n // tile["j"]) * (k // tile["kk"])


#: time one grid step of the planned matmul costs beyond its DMA and MXU
#: work: 0.265 µs, the least-squares fit of ``python -m
#: repro.kernels.bench --chip`` over 45 tiles at granite_3_2b's MLP
#: shapes on a TPU v5e
GRID_STEP_S = 0.265e-6


def matmul_time_s(m: int, n: int, k: int, tile: Dict[str, int]) -> float:
    """Estimated time of the planned matmul kernel at ``tile``: the
    larger of its HBM bytes over bandwidth and its FLOPs over peak, plus
    its grid steps times :data:`GRID_STEP_S`."""
    return (max(matmul_hbm_bytes(m, n, k, tile) / HBM_BW,
                2 * m * n * k / PEAK_FLOPS)
            + matmul_grid_steps(m, n, k, tile) * GRID_STEP_S)


def _fit_matmul_plan(plan: KernelPlan, m: int, n: int, k: int
                     ) -> KernelPlan:
    """Re-fit a lowered matmul plan's tiles to the one of
    :func:`matmul_tiles` with the least :func:`matmul_time_s`.  The
    schedule's loop order and lane dim stay; only the tiles change.
    Where no tile fits the budget, the lowered tiles stand."""
    tiles = matmul_tiles(m, n, k)
    if not tiles:
        return plan
    return replace(plan, tile=min(
        tiles, key=functools.partial(matmul_time_s, m, n, k)))


@_plan_memo("attention")
def plan_attention(seq_q: int, seq_k: int, head_dim: int) -> KernelPlan:
    """Schedule the S = Q·Kᵀ core (q, k, d loops): contiguity puts d
    innermost (lanes) and yields the q-block × k-block band that the
    flash kernel tiles over."""
    remote = _remote_plan("attention", seq_q, seq_k, head_dim)
    if remote is not None:
        return remote
    s = Scop("attn_score", params={"Q": seq_q, "K": seq_k, "D": head_dim})
    with s.loop("q", 0, "Q"):
        with s.loop("kk", 0, "K"):
            with s.loop("d", 0, "D"):
                s.stmt("S[q,kk] = S[q,kk] + Qm[q,d] * Km[kk,d]")
    cfg = tensor_style()
    sched = schedule_with_ladder(s, cfg, cache=global_cache(),
                                 with_tree=True)
    plan = lower_to_kernel_plan(schedule_tree(sched), sched=sched)
    # flash blocking: q and k tiles bounded for the online-softmax state
    tile = dict(plan.tile)
    tile["q"] = min(tile.get("q", 128), 128)
    tile["kk"] = min(tile.get("kk", 128), 128)
    return replace(plan, tile=tile)


@_plan_memo("mamba_scan")
def plan_mamba_scan(seq: int, d_inner: int, state: int) -> KernelPlan:
    """Selective-scan (Mamba-1) recurrence h_t = a_t ⊙ h_{t-1} + b_t with
    y_t = h_t · c_t: the scheduler discovers t sequential-outermost (the
    recurrence dependence) with the d/state dims parallel inside, and the
    lowering turns that into the kernel's chunked grid — chunk size from
    the t tile, d-block from the d tile."""
    remote = _remote_plan("mamba_scan", seq, d_inner, state)
    if remote is not None:
        return remote
    s = Scop("mamba_scan", params={"T": seq, "D": d_inner, "S": state})
    with s.loop("t", 0, "T"):
        with s.loop("d", 0, "D"):
            with s.loop("n", 0, "S"):
                s.stmt("H[d,n] = A[t,d,n] * H[d,n] + B[t,d,n]")
                s.stmt("Y[t,d] = Y[t,d] + H[d,n] * Cs[t,n]")
    cfg = tensor_style()
    sched = schedule_with_ladder(s, cfg, cache=global_cache(),
                                 with_tree=True)
    plan = lower_to_kernel_plan(schedule_tree(sched), stmt_idx=0,
                                sched=sched)
    return _fit_scan_plan(s, plan, fused=False)


def _fit_scan_plan(scop: Scop, plan: KernelPlan, fused: bool) -> KernelPlan:
    """Re-fit a lowered SSM plan's tiles to the kernel's real blocks
    (:func:`scan_block_bytes`).  The hidden state (state × d_block) is
    VMEM-resident scratch across chunks, so the state dim stays whole,
    pinned *inside* the fit so t/d shrink against the true footprint;
    d rides the lanes, so its tile stays a whole lane width.  In the
    fused kernel d carries no dependence and its tile starts as wide as
    one recurrence step's values — h, a_t, b_t and h·c_t, each a
    (state, d) f32 tile — fit the vector registers, so the serial h
    chain has the most independent lanes per step."""
    stmt = scop.statements[0]
    dims = _iter_extents(scop, stmt)
    n, d = dims["n"], dims["d"]
    start = None
    if fused:
        while (4 * -(-n // SUBLANE) * SUBLANE * d * 4 > VREG_FILE_BYTES
               and d % (2 * LANE) == 0):
            d //= 2
        start = {"d": d}
    tile = _fit_tiles(list(plan.loop_order), dims, plan.vector_iter, stmt,
                      fixed={"n": n}, floor={"d": LANE}, start=start,
                      block_bytes=functools.partial(scan_block_bytes,
                                                    fused=fused))
    return replace(plan, tile=tile)


def _scan_gate_scop(seq: int, d_inner: int, state: int) -> Scop:
    """Fused Mamba tail: the discretised recurrence + C-contraction
    (3-deep, reading Δ, A, B and x, not a (t, d, n) a/b) and the
    skip+gate epilogue (2-deep) share one t/d nest, so the scheduler
    sees the fusion and tiles t/d for the combined working set."""
    s = Scop("scan_gate", params={"T": seq, "D": d_inner, "S": state})
    with s.loop("t", 0, "T"):
        with s.loop("d", 0, "D"):
            with s.loop("n", 0, "S"):
                s.stmt("H[d,n] = exp(Dt[t,d] * Am[d,n]) * H[d,n]"
                       " + Dt[t,d] * X[t,d] * Bs[t,n]")
                s.stmt("Y[t,d] = Y[t,d] + H[d,n] * Cs[t,n]")
            s.stmt("O[t,d] = (Y[t,d] + X[t,d] * Dk[d]) * G[t,d]")
    return s


@_plan_memo("scan_gate")
def plan_scan_gate(seq: int, d_inner: int, state: int) -> KernelPlan:
    """Plan the fused scan+skip+gate kernel (``repro.kernels.scan_gate``).

    Unlike the single-schedule planners this one is *autotuned*: the
    fused SCoP's schedule bases are enumerated and statically ranked by
    :func:`repro.core.autotune.rank_pallas_plans` (the PolyTOPS
    reconfigurability story — the cost model picks among legal
    schedules), and the best lowerable candidate's t/d tiles become the
    kernel's chunk/d_block.  Falls back to the ladder path on any
    autotune failure so planning stays total."""
    remote = _remote_plan("scan_gate", seq, d_inner, state)
    if remote is not None:
        return remote
    scop = _scan_gate_scop(seq, d_inner, state)
    plan: Optional[KernelPlan] = None
    try:
        from .autotune import rank_pallas_plans

        cands = rank_pallas_plans(scop, top_k=4, cache=global_cache())
        for cand in cands:
            if cand.plan is not None and "t" in cand.plan.tile \
                    and "d" in cand.plan.tile:
                plan = cand.plan
                break
    except Exception:
        plan = None
    if plan is None:
        cfg = tensor_style()
        sched = schedule_with_ladder(scop, cfg, cache=global_cache(),
                                     with_tree=True)
        plan = lower_to_kernel_plan(schedule_tree(sched), stmt_idx=0,
                                    sched=sched)
    return _fit_scan_plan(scop, plan, fused=True)
