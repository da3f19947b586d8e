"""Pallas kernel microbenchmarks + CI smoke gate.

On this CPU container the kernels execute in interpret mode, so absolute
times are NOT TPU times — the CSV reports (a) interpret-mode sanity
timings, (b) the PolyTOPS plan for each kernel (the actual deliverable:
grid order/tiles), and (c) the XLA-reference timing for context.

``python -m repro.kernels.bench --chip`` times the planned matmul on a
TPU at granite_3_2b's MLP shapes (:func:`chip`); it exits 2 elsewhere.

``python -m repro.kernels.bench --smoke`` is the JAX-CPU smoke gate run
by ``scripts/tier1.sh`` / CI: every kernel executes through the
schedule-tree → ``lower_to_kernel_plan`` lowering (interpret mode) and
must numerically match its pure-jnp oracle in ``repro.kernels.ref`` —
exit status 1 on any mismatch.
"""
from __future__ import annotations

import argparse
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np

from ..core import akg
from ..core.akg import (plan_attention, plan_matmul, plan_mamba_scan,
                        plan_scan_gate)
from ..launch.roofline import HBM_BW, PEAK_FLOPS
from ..model.ssm import discretise
from . import matmul_polytops, ops, ref


def _time(fn, *args, reps=3):
    fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.time() - t0) / reps * 1e6


def run(out=sys.stdout):
    print("kernel,us_per_call,plan", file=out)
    r = jax.random.PRNGKey(0)
    for m, n, k in [(256, 256, 256), (512, 512, 512)]:
        a = jax.random.normal(r, (m, k), jnp.float32)
        b = jax.random.normal(jax.random.fold_in(r, 1), (k, n), jnp.float32)
        plan = plan_matmul(m, n, k)
        t_i = _time(lambda x, y: ops.matmul(x, y), a, b, reps=1)
        t_x = _time(lambda x, y: ref.matmul_ref(x, y), a, b)
        print(f"matmul_{m}x{n}x{k}_interpret,{t_i:.1f},"
              f"order={'>'.join(plan.loop_order)} tiles={plan.tile}", file=out)
        print(f"matmul_{m}x{n}x{k}_xla_ref,{t_x:.1f},-", file=out)
    b_, s, h, d = 1, 512, 4, 64
    q = jax.random.normal(r, (b_, s, h, d), jnp.float32) * 0.3
    kk = jax.random.normal(jax.random.fold_in(r, 2), (b_, s, h, d), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(r, 3), (b_, s, h, d), jnp.float32)
    plan = plan_attention(s, s, d)
    t_i = _time(lambda *x: ops.flash_attention(*x), q, kk, v, reps=1)
    print(f"flash_attn_{s}_interpret,{t_i:.1f},"
          f"bq={plan.tile['q']} bk={plan.tile['kk']} lanes={plan.vector_iter}",
          file=out)
    a_bar = jax.nn.sigmoid(jax.random.normal(r, (1, 128, 256, 16))) * 0.9
    b_bar = jax.random.normal(jax.random.fold_in(r, 4), (1, 128, 256, 16)) * 0.1
    c = jax.random.normal(jax.random.fold_in(r, 5), (1, 128, 16))
    plan = plan_mamba_scan(128, 256, 16)
    t_i = _time(lambda *x: ops.selective_scan(*x), a_bar, b_bar, c, reps=1)
    print(f"mamba_scan_128_interpret,{t_i:.1f},"
          f"chunk={plan.tile['t']} dblock={plan.tile['d']} state-in-VMEM",
          file=out)
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(r, 9),
                                           (1, 128, 256)))
    A = -jnp.broadcast_to(jnp.arange(1, 17, dtype=jnp.float32), (256, 16))
    bm = jax.random.normal(jax.random.fold_in(r, 4), (1, 128, 16))
    x_skip = jax.random.normal(jax.random.fold_in(r, 6), (1, 128, 256))
    dk = jax.random.normal(jax.random.fold_in(r, 7), (256,))
    z = jax.random.normal(jax.random.fold_in(r, 8), (1, 128, 256))
    plan = plan_scan_gate(128, 256, 16)
    t_i = _time(lambda *x: ops.scan_gate(*x)[0], dt, A, bm, c, x_skip,
                dk, z, reps=1)
    print(f"scan_gate_128_interpret,{t_i:.1f},"
          f"chunk={plan.tile['t']} dblock={plan.tile['d']} fused-gate",
          file=out)


def smoke(out=sys.stdout) -> int:
    """CI gate: run every Pallas kernel (small shapes, interpret mode)
    through the schedule-tree lowering and check numerical agreement
    with the pure-jnp oracles.  Returns the number of failures."""
    failures = 0
    r = jax.random.PRNGKey(0)

    def check(name, got, want, tol):
        nonlocal failures
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        ok = np.allclose(got, want, rtol=tol, atol=tol)
        print(f"{name},{'PASS' if ok else 'FAIL'},max_abs_err={err:.3e}",
              file=out)
        if not ok:
            failures += 1

    m = n = k = 128
    a = jax.random.normal(r, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(r, 1), (k, n), jnp.float32)
    plan = plan_matmul(m, n, k)
    print(f"plan_matmul,{'>'.join(plan.loop_order)},vec={plan.vector_iter} "
          f"tiles={plan.tile}", file=out)
    check("matmul_smoke", ops.matmul(a, b, interpret=True),
          ref.matmul_ref(a, b), 1e-4)

    bsz, s, h, d = 1, 128, 2, 64
    q = jax.random.normal(r, (bsz, s, h, d), jnp.float32) * 0.3
    kk = jax.random.normal(jax.random.fold_in(r, 2), (bsz, s, h, d),
                           jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(r, 3), (bsz, s, h, d),
                          jnp.float32)
    plan = plan_attention(s, s, d)
    print(f"plan_attention,{'>'.join(plan.loop_order)},vec={plan.vector_iter} "
          f"tiles={plan.tile}", file=out)
    got = ops.flash_attention(q, kk, v, causal=True, interpret=True)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3).reshape(bsz * h, s, d),
        kk.transpose(0, 2, 1, 3).reshape(bsz * h, s, d),
        v.transpose(0, 2, 1, 3).reshape(bsz * h, s, d),
        causal=True).reshape(bsz, h, s, d).transpose(0, 2, 1, 3)
    check("flash_attention_smoke", got, want, 1e-4)

    bsz, s, di, st = 1, 64, 128, 8
    a_bar = jax.nn.sigmoid(jax.random.normal(r, (bsz, s, di, st))) * 0.9
    b_bar = jax.random.normal(jax.random.fold_in(r, 4),
                              (bsz, s, di, st)) * 0.1
    c = jax.random.normal(jax.random.fold_in(r, 5), (bsz, s, st))
    plan = plan_mamba_scan(s, di, st)
    print(f"plan_mamba_scan,{'>'.join(plan.loop_order)},"
          f"vec={plan.vector_iter} tiles={plan.tile}", file=out)
    check("mamba_scan_smoke", ops.selective_scan(a_bar, b_bar, c,
                                                 interpret=True),
          ref.selective_scan_ref(a_bar, b_bar, c), 1e-4)

    # fused discretisation+scan+skip+gate kernel (autotuned via
    # rank_pallas_plans) against the jnp route's discretisation and the
    # fused oracle, full-sequence and chunked with the h0 state carry
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(r, 9),
                                           (bsz, s, di)) - 1.0)
    A = -jnp.broadcast_to(jnp.arange(1, st + 1, dtype=jnp.float32), (di, st))
    bm = jax.random.normal(jax.random.fold_in(r, 10), (bsz, s, st))
    x_skip = jax.random.normal(jax.random.fold_in(r, 6), (bsz, s, di))
    dk = jax.random.normal(jax.random.fold_in(r, 7), (di,))
    z = jax.random.normal(jax.random.fold_in(r, 8), (bsz, s, di))
    plan = plan_scan_gate(s, di, st)
    print(f"plan_scan_gate,{'>'.join(plan.loop_order)},"
          f"vec={plan.vector_iter} tiles={plan.tile}", file=out)
    o_got, h_got = ops.scan_gate(dt, A, bm, c, x_skip, dk, z,
                                 interpret=True)
    o_want, h_want = ref.scan_gate_ref(*discretise(dt, A, bm, x_skip), c,
                                       x_skip, dk, z)
    check("scan_gate_smoke", o_got, o_want, 1e-4)
    check("scan_gate_state_smoke", h_got, h_want, 1e-4)
    m_ = s // 2
    _, h1 = ops.scan_gate(dt[:, :m_], A, bm[:, :m_], c[:, :m_],
                          x_skip[:, :m_], dk, z[:, :m_], interpret=True)
    o2, h2 = ops.scan_gate(dt[:, m_:], A, bm[:, m_:], c[:, m_:],
                           x_skip[:, m_:], dk, z[:, m_:], h0=h1,
                           interpret=True)
    check("scan_gate_chunk_carry_smoke", o2, o_want[:, m_:], 1e-4)
    check("scan_gate_chunk_state_smoke", h2, h_want, 1e-4)

    print(f"pallas_smoke,{'PASS' if not failures else 'FAIL'},"
          f"failures={failures}", file=out)
    return failures


#: granite_3_2b's MLP products of one 256-row prefill chunk: gate and up
#: (rows x d_model . d_model x d_ff), then down, as (M, N, K)
MLP_SHAPES = ((256, 8192, 2048), (256, 2048, 8192))
#: distinct weights per timed program, as the layers of a model walk
LAYERS = 16


def _per_call_us(fn, a, ws, reps=10, rounds=3) -> float:
    """Device-bound time of one ``fn(a, w)``, µs: one jitted program
    runs it on each of ``ws`` in turn (distinct weights, so no call is
    merged or hoisted); ``reps`` programs are queued before one wait, so
    the host's dispatch overlaps the device's work; warm, best of
    ``rounds``."""
    f = jax.jit(lambda a, ws: [fn(a, w) for w in ws])
    jax.block_until_ready(f(a, ws))
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready([f(a, ws) for _ in range(reps)])
        best = min(best, time.perf_counter() - t0)
    return best / (reps * len(ws)) * 1e6


def chip(out=sys.stdout) -> int:
    """Time the planned matmul at :data:`MLP_SHAPES` on a TPU: at the
    general lowering's tile, at the fitted tile, at every tile the fit
    chooses among with at least half the rows and at most 64 grid steps,
    and ``jnp.dot``; then fit the per-step cost that
    ``akg.GRID_STEP_S`` holds (least squares of µs over HBM bytes and
    grid steps).  Roofline share: the larger of the product's FLOPs over
    peak and its operands' bytes over bandwidth, over the time."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"--chip needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    print(f"device,{dev.device_kind},grid_step_s_now,{akg.GRID_STEP_S}",
          file=out)
    print("m,n,k,kernel,bm,bn,bk,grid_steps,hbm_mb,est_us,us,roofline_pct",
          file=out)
    fit_rows, fit_us = [], []
    for m, n, k in MLP_SHAPES:
        r = jax.random.PRNGKey(m + n + k)
        a = jax.random.normal(r, (m, k), jnp.bfloat16)
        ws = [jax.random.normal(jax.random.fold_in(r, i), (k, n),
                                jnp.bfloat16) for i in range(LAYERS)]
        least = max(2 * m * n * k / PEAK_FLOPS,
                    2 * (m * k + k * n + m * n) / HBM_BW)
        plan = plan_matmul(m, n, k)
        lowered = akg.lower_matmul(m, n, k).tile
        runs = [("lowered", lowered), ("fitted", plan.tile)] + [
            ("sweep", t) for t in akg.matmul_tiles(m, n, k)
            if 2 * t["i"] >= m and akg.matmul_grid_steps(m, n, k, t) <= 64
            and t not in (lowered, plan.tile)]
        for name, tile in runs:
            p = replace(plan, tile=tile)
            us = _per_call_us(lambda x, w, p=p: matmul_polytops.matmul(
                x, w, plan=p, interpret=False), a, ws)
            nb = akg.matmul_hbm_bytes(m, n, k, tile)
            steps = akg.matmul_grid_steps(m, n, k, tile)
            fit_rows.append([1.0, nb, steps])
            fit_us.append(us)
            print(f"{m},{n},{k},{name},{tile['i']},{tile['j']},"
                  f"{tile['kk']},{steps},{nb / 1e6:.3f},"
                  f"{akg.matmul_time_s(m, n, k, tile) * 1e6:.2f},"
                  f"{us:.2f},{100 * least / (us * 1e-6):.2f}", file=out)
        us = _per_call_us(jnp.dot, a, ws)
        print(f"{m},{n},{k},jnp.dot,,,,,,,{us:.2f},"
              f"{100 * least / (us * 1e-6):.2f}", file=out)
    (c0, per_byte, per_step), *_ = np.linalg.lstsq(
        np.array(fit_rows), np.array(fit_us), rcond=None)
    print(f"fit,overhead_us,{c0:.3f},gb_per_s,{1e-3 / per_byte:.1f},"
          f"grid_step_us,{per_step:.4f}", file=out)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="run the numerical smoke gate instead of timings")
    ap.add_argument("--chip", action="store_true",
                    help="time the planned matmul's tiles on a TPU")
    args = ap.parse_args(argv)
    if args.smoke:
        return 1 if smoke() else 0
    if args.chip:
        return chip()
    run()
    return 0


if __name__ == "__main__":
    sys.exit(main())
