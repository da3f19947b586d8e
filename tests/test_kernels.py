"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.akg import plan_attention, plan_matmul
from repro.kernels import matmul_polytops, ops, ref


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 128),
                                   (64, 256, 512), (32, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_allclose(m, n, k, dtype):
    r = jax.random.PRNGKey(0)
    a = jax.random.normal(r, (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(r, 1), (k, n), dtype)
    got = np.asarray(ops.matmul(a, b, interpret=True), np.float32)
    want = np.asarray(ref.matmul_ref(a, b), np.float32)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * k ** 0.5)


def test_matmul_plan_is_polytops_derived():
    plan = plan_matmul(256, 256, 256)
    assert plan.loop_order[0] == "i"
    assert plan.loop_order[-1] == "j"        # lanes innermost (contiguity)
    assert plan.vector_iter == "j"
    assert plan.tile["j"] % 128 == 0 or plan.tile["j"] == 256


@pytest.mark.parametrize("b,s,h,hkv,d", [(2, 256, 4, 2, 64), (1, 128, 2, 2, 32),
                                         (2, 64, 4, 4, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_allclose(b, s, h, hkv, d, causal):
    r = jax.random.PRNGKey(1)
    q = jax.random.normal(r, (b, s, h, d), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(r, 2), (b, s, hkv, d), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(r, 3), (b, s, hkv, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    rep = h // hkv
    kr, vr = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        kr.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        vr.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        causal=causal).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,s,di,st", [(2, 64, 256, 16), (1, 128, 128, 8),
                                       (1, 44, 128, 8)])
def test_selective_scan_allclose(b, s, di, st):
    r = jax.random.PRNGKey(2)
    a_bar = jax.nn.sigmoid(jax.random.normal(r, (b, s, di, st))) * 0.9
    b_bar = jax.random.normal(jax.random.fold_in(r, 4), (b, s, di, st)) * 0.1
    c = jax.random.normal(jax.random.fold_in(r, 5), (b, s, st))
    got = ops.selective_scan(a_bar, b_bar, c, interpret=True)
    want = ref.selective_scan_ref(a_bar, b_bar, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _ssm_operands(b, s, di, st, seed=3):
    """scan_gate's operands as the model hands them over: Δ after
    softplus, A = -exp(a_log) over Mamba's 1..st init, B, C, x, d_skip,
    z and an initial state."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt = jax.nn.softplus(jax.random.normal(k[0], (b, s, di)) - 1.0)
    A = -jnp.broadcast_to(jnp.arange(1, st + 1, dtype=jnp.float32), (di, st))
    B = jax.random.normal(k[1], (b, s, st))
    C = jax.random.normal(k[2], (b, s, st))
    x = jax.random.normal(k[3], (b, s, di))
    dk = jax.random.normal(k[4], (di,))
    z = jax.random.normal(k[5], (b, s, di))
    h0 = jax.random.normal(k[6], (b, di, st))
    return dt, A, B, C, x, dk, z, h0


def _scan_gate_want(dt, A, B, C, x, dk, z, h0=None):
    """The jnp route: discretise to a_bar/b_bar, then the fused oracle."""
    from repro.model.ssm import discretise
    return ref.scan_gate_ref(*discretise(dt, A, B, x), C, x, dk, z, h0=h0)


@pytest.mark.parametrize("b,s,di,st", [(2, 64, 256, 16), (1, 128, 128, 8),
                                       (1, 44, 128, 16)])
@pytest.mark.parametrize("with_h0", [False, True])
def test_scan_gate_discretises_like_the_jnp_route(b, s, di, st, with_h0):
    """The kernel builds a_t = exp(Δ·A) and b_t = Δ·x·B itself; 44 rows
    pad to a GROUP multiple with Δ = 0 steps, which leave h exact."""
    dt, A, B, C, x, dk, z, h0 = _ssm_operands(b, s, di, st)
    h0 = h0 if with_h0 else None
    o, h = ops.scan_gate(dt, A, B, C, x, dk, z, h0=h0, interpret=True)
    o_want, h_want = _scan_gate_want(dt, A, B, C, x, dk, z, h0)
    assert o.shape == x.shape and h.shape == (b, di, st)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_want),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk", [None, 16])
def test_scan_gate_two_chunk_carry_matches_one_pass(chunk):
    """Two calls carrying h_last into h0 give the one-pass output and
    final state; ``chunk`` 16 also carries the state across the
    kernel's own sequence grid."""
    from repro.kernels import scan_gate as sg
    dt, A, B, C, x, dk, z, h0 = _ssm_operands(2, 96, 256, 16, seed=4)
    o_want, h_want = _scan_gate_want(dt, A, B, C, x, dk, z, h0)
    m = 40
    halves = [tuple(v[:, sl] for v in (dt, B, C, x, z))
              for sl in (slice(None, m), slice(m, None))]
    h = h0
    outs = []
    for dt_, B_, C_, x_, z_ in halves:
        o, h = sg.scan_gate(dt_, A, B_, C_, x_, dk, z_, h0=h, chunk=chunk,
                            interpret=True)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(o_want), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_want),
                               rtol=1e-4, atol=1e-4)


def test_attention_plan_lanes():
    plan = plan_attention(512, 512, 128)
    assert plan.vector_iter == "d"           # head_dim on lanes
    assert plan.tile["q"] <= 128 and plan.tile["kk"] <= 128


# ---------------------------------------------------------------------------
# KernelPlan lowering properties: every kernel's scheduler-produced tree
# lowers to a TPU-legal plan — lane-aligned vector dim, sublane-aligned
# next-inner dim, VMEM-fitting tiles.
# ---------------------------------------------------------------------------

from repro.core.akg import (LANE, SUBLANE, VMEM_BYTES,  # noqa: E402
                            lower_to_kernel_plan, plan_mamba_scan,
                            plan_scan_gate, scan_block_bytes)
from repro.core.cachemodel import (stmt_access_groups,  # noqa: E402
                                   working_set_bytes)


def _assert_tpu_legal(plan, scop, stmt_idx, dims, bytes_per_elem, n_buffers):
    stmt = scop.statements[stmt_idx]
    # grid order covers every iterator exactly once
    assert sorted(plan.loop_order) == sorted(stmt.iters)
    vec = plan.vector_iter
    assert vec in plan.loop_order
    # lane alignment on the vector dim (or the whole dim when small)
    tv = plan.tile[vec]
    assert tv % LANE == 0 or tv == dims[vec], (plan, dims)
    # sublane alignment on the next-inner non-vector dim
    inner = [it for it in plan.loop_order if it != vec]
    if inner:
        ti = plan.tile[inner[-1]]
        assert ti % SUBLANE == 0 or ti == dims[inner[-1]], (plan, dims)
    # the tile working set (real access groups, buffered) fits VMEM
    groups = stmt_access_groups(stmt, list(plan.loop_order))
    sizes = [plan.tile[it] for it in plan.loop_order]
    ws = n_buffers * working_set_bytes(groups, sizes, bytes_per_elem)
    assert ws <= VMEM_BYTES, (plan, ws)


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 128),
                                   (512, 512, 512), (64, 256, 512),
                                   (1024, 1024, 512), (2048, 2048, 2048),
                                   (256, 8192, 2048), (256, 2048, 8192)])
def test_matmul_plan_tpu_legal(m, n, k):
    from repro.core.akg import _matmul_scop
    plan = plan_matmul(m, n, k)
    _assert_tpu_legal(plan, _matmul_scop(m, n, k), 0,
                      {"i": m, "j": n, "kk": k}, 2, 3)


@pytest.mark.parametrize("m,n,k", [(256, 8192, 2048), (256, 2048, 8192)])
def test_matmul_plan_streams_each_weight_once(m, n, k):
    """At granite_3_2b's MLP shapes (a 256-row prefill chunk: gate/up,
    then down) the fitted plan keeps every row in one tile, so the
    weight is read once, in few grid steps, and the HBM bytes its
    BlockSpecs move stay near the operands' own."""
    from repro.core.akg import matmul_grid_steps, matmul_hbm_bytes
    tile = plan_matmul(m, n, k).tile
    assert tile["i"] == 256
    assert matmul_grid_steps(m, n, k, tile) <= 32
    assert matmul_hbm_bytes(m, n, k, tile) <= 1.1 * 2 * (m * k + k * n
                                                        + m * n)


@pytest.mark.parametrize("m,n,k,k_steps", [(64, 256, 512, 1),
                                           (64, 256, 16384, 4)])
def test_matmul_matches_oracle_at_fitted_plan(m, n, k, k_steps):
    """The kernel at the fitted plan: one k step with the A panel
    resident, and the whole N in one lane tile with the f32 accumulator
    carried over several k steps."""
    plan = plan_matmul(m, n, k)
    assert k // plan.tile["kk"] == k_steps
    assert k_steps == 1 or plan.tile["j"] == n
    r = jax.random.PRNGKey(3)
    a = jax.random.normal(r, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(r, 1), (k, n), jnp.float32)
    got = matmul_polytops.matmul(a, b, plan=plan, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-4 * k ** 0.5)


@pytest.mark.parametrize("sq,sk,d", [(128, 128, 64), (512, 512, 128),
                                     (256, 1024, 128), (1024, 1024, 64)])
def test_attention_plan_tpu_legal(sq, sk, d):
    plan = plan_attention(sq, sk, d)
    dims = {"q": sq, "kk": sk, "d": d}
    assert plan.vector_iter == "d"
    assert plan.tile["d"] % LANE == 0 or plan.tile["d"] == d
    assert plan.tile["q"] <= 128 and plan.tile["kk"] <= 128
    assert all(plan.tile[it] % SUBLANE == 0 or plan.tile[it] == dims[it]
               for it in plan.loop_order)


@pytest.mark.parametrize("seq,di,st", [(64, 128, 8), (128, 256, 16),
                                       (256, 512, 32), (512, 1024, 16),
                                       (256, 256, 255), (128, 2048, 256)])
def test_mamba_plan_tpu_legal(seq, di, st):
    plan = plan_mamba_scan(seq, di, st)
    # t is the recurrence dim: sequential, outermost in the grid order
    assert plan.loop_order[0] == "t"
    assert plan.tile["n"] == st            # hidden state untiled (VMEM)
    assert plan.tile["d"] % LANE == 0 or plan.tile["d"] == di  # d on lanes
    assert plan.tile["t"] <= seq
    # the pinned state dim counts against the budget: the kernel's real
    # blocks (padded, double-buffered) fit VMEM even for
    # non-lane-multiple states
    assert scan_block_bytes(plan.tile, fused=False) <= VMEM_BYTES, plan
    assert scan_block_bytes(plan_scan_gate(seq, di, st).tile,
                            fused=True) <= VMEM_BYTES


@pytest.mark.parametrize("seq,st,d_tile", [(256, 16, 1024), (44, 16, 1024),
                                           (256, 8, 2048)])
def test_scan_gate_plan_fills_the_vector_registers(seq, st, d_tile):
    """At d_inner 8192 the fused plan's d tile is the widest whose
    recurrence step (h, a_t, b_t and h·c_t, (state, d) f32 each) fits
    the vector registers: at state 16 that is 1024 lanes, the fastest
    of d_block 128 to 4096 timed on a TPU v5e."""
    from repro.core.akg import VREG_FILE_BYTES
    tile = plan_scan_gate(seq, 8192, st).tile
    assert tile == {"d": d_tile, "t": min(seq, 128), "n": st}
    step = 4 * max(st, SUBLANE) * d_tile * 4
    assert step <= VREG_FILE_BYTES < 2 * step
    assert scan_block_bytes(tile, fused=True) <= VMEM_BYTES


def _pallas_vmem_bytes(fn, *args) -> int:
    """VMEM the one pallas_call in ``fn`` holds, read from its own block
    mappings: each block's two minor dims padded to the (8, 128) vreg
    tile and counted as f32, double-buffered, plus the scratch."""
    import math
    eqn = next(e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
               if e.primitive.name == "pallas_call")
    gm = eqn.params["grid_mapping"]

    def padded(shape):
        *lead, rows, cols = shape
        return (math.prod(lead) * -(-rows // SUBLANE) * SUBLANE
                * -(-cols // LANE) * LANE * 4)

    return (2 * sum(padded(bm.block_aval.shape) for bm in gm.block_mappings)
            + sum(padded(a.shape) for a in gm.scratch_avals))


@pytest.mark.parametrize("chunk,d_block", [(128, 128), (256, 512),
                                           (48, 256)])
def test_scan_block_bytes_mirrors_the_kernels_blockspecs(chunk, d_block):
    """The planner's footprint is the kernels' own: scan_gate's Δ, x, z,
    o rows, B/C columns, A, h0/h_out and skip blocks; selective_scan's
    a/b blocks, c columns and y rows."""
    from repro.kernels import mamba_scan as ms
    from repro.kernels import scan_gate as sg
    f32 = jnp.float32
    b, di, st = 1, 1024, 16
    tile = {"t": chunk, "d": d_block, "n": st}
    rows, cols = (jax.ShapeDtypeStruct((b, chunk, n), f32)
                  for n in (di, st))
    fused = _pallas_vmem_bytes(
        lambda dt, A, B, C, x, dk, z: sg.scan_gate(
            dt, A, B, C, x, dk, z, d_block=d_block, chunk=chunk,
            interpret=True),
        rows, jax.ShapeDtypeStruct((di, st), f32), cols, cols, rows,
        jax.ShapeDtypeStruct((di,), f32), rows)
    assert scan_block_bytes(tile, fused=True) == fused
    ab = jax.ShapeDtypeStruct((b, chunk, di, st), f32)
    plain = _pallas_vmem_bytes(
        lambda a, bb, c: ms.selective_scan(a, bb, c, d_block=d_block,
                                           chunk=chunk, interpret=True),
        ab, ab, cols)
    assert scan_block_bytes(tile, fused=False) == plain


@pytest.mark.parametrize("tile", [{"i": 256, "j": 512, "kk": 2048},
                                  {"i": 256, "j": 2048, "kk": 512},
                                  {"i": 64, "j": 128, "kk": 256}])
def test_matmul_block_bytes_mirrors_the_kernels_blockspecs(tile):
    """The matmul fit's VMEM footprint is the kernel's own: A, B and C
    blocks double-buffered, counted as f32, plus the f32 accumulator."""
    from dataclasses import replace
    from repro.core.akg import matmul_block_bytes
    m, n, k = 2 * tile["i"], 2 * tile["j"], 2 * tile["kk"]
    plan = replace(plan_matmul(m, n, k), tile=tile)
    got = _pallas_vmem_bytes(
        lambda a, b: matmul_polytops.matmul(a, b, plan=plan,
                                            interpret=True),
        jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
        jax.ShapeDtypeStruct((k, n), jnp.bfloat16))
    assert matmul_block_bytes(tile) == got


def test_mamba_kernel_consumes_scheduler_plan():
    """selective_scan's default block geometry comes from the schedule
    tree (no hand-coded order/tiles) and still matches the oracle."""
    import repro.kernels.mamba_scan as ms
    plan = plan_mamba_scan(64, 128, 8)
    r = jax.random.PRNGKey(7)
    a_bar = jax.nn.sigmoid(jax.random.normal(r, (1, 64, 128, 8))) * 0.9
    b_bar = jax.random.normal(jax.random.fold_in(r, 1), (1, 64, 128, 8)) * 0.1
    c = jax.random.normal(jax.random.fold_in(r, 2), (1, 64, 8))
    got = ms.selective_scan(a_bar, b_bar, c,     # plan-driven defaults
                            interpret=True)
    explicit = ms.selective_scan(a_bar, b_bar, c,
                                 d_block=plan.tile["d"],
                                 chunk=plan.tile["t"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(explicit),
                               rtol=0, atol=0)
    want = ref.selective_scan_ref(a_bar, b_bar, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_plan_wrappers_are_thin_over_general_lowering():
    """plan_matmul is the general tree lowering followed by the matmul
    tile fit, nothing more: the schedule's loop order and lanes stay."""
    from repro.core.akg import _fit_matmul_plan, _matmul_scop
    from repro.core.config import tensor_style
    from repro.core.schedcache import cached_schedule_scop
    from repro.core.schedtree import schedule_tree

    m, n, k = 256, 8192, 2048
    scop = _matmul_scop(m, n, k)
    cfg = tensor_style()
    cfg.auto_vectorize = True
    sched = cached_schedule_scop(scop, cfg)
    lowered = lower_to_kernel_plan(schedule_tree(sched))
    plan = plan_matmul(m, n, k)
    assert _fit_matmul_plan(lowered, m, n, k) == plan
    assert (plan.loop_order, plan.vector_iter) == (lowered.loop_order,
                                                   lowered.vector_iter)
