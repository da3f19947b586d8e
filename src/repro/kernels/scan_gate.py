"""Fused selective-scan + skip + SiLU-gate Pallas kernel (Mamba block tail).

One kernel discretises the recurrence and computes what the jnp path
spreads over several ops:

    a_t = exp(Δ_t ⊙ A),  b_t = (Δ_t ⊙ x_t) ⊗ B_t     (discretisation)
    h_t = a_t ⊙ h_{t-1} + b_t                      (recurrence)
    y_t = h_t · c_t + x_t ⊙ d_skip                 (contraction + skip)
    o_t = y_t ⊙ silu(z_t)                          (gate)

It reads the discretisation's inputs — Δ and x (b, s, d), A (d, n), B
and C (b, s, n) — and builds a_t/b_t in f32 in VMEM one step at a time,
so the (b, s, d, n) coefficients never reach HBM.  The hidden state
(state × d_block) stays VMEM-resident across sequence chunks from an
explicit initial state ``h0`` — the carry that lets a serving engine
process a prompt in chunks (continuous batching) — and the final state
is returned for the next chunk.  The TPU layout (d on lanes, state on
sublanes, aligned groups of ``GROUP`` rows) is :mod:`.mamba_scan`'s.

Block geometry comes from the scheduler: ``repro.core.akg.plan_scan_gate``
builds the fused SCoP (recurrence + gate statement in one t/d nest),
ranks the enumerated schedule bases with
:func:`repro.core.autotune.rank_pallas_plans`, and lowers the winner
through the same ``lower_to_kernel_plan`` bridge as every other kernel —
chunk = the t tile, d_block = the d tile.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mode import resolve_interpret
from .mamba_scan import GROUP, block_geometry


def _kernel(dt_ref, a_ref, b_ref, c_ref, x_ref, dk_ref, z_ref, h0_ref,
            o_ref, hout_ref, h_ref, *, chunk: int, n_chunks: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = h0_ref[0]

    A = a_ref[...]                                       # (st, bd)
    dk = dk_ref[...]                                     # (1, bd)
    rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, A.shape[1]), 0)

    def group(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        dt = dt_ref[0, pl.ds(t0, GROUP), :]              # (GROUP, bd)
        x = x_ref[0, pl.ds(t0, GROUP), :].astype(jnp.float32)
        dtx = dt * x
        y = jnp.zeros_like(dt)
        for j in range(GROUP):
            a = jnp.exp(dt[j:j + 1] * A)                 # (st, bd)
            h = a * h + dtx[j:j + 1] * b_ref[0, t0 + j]  # B_t: (st, 1)
            yj = jnp.sum(h * c_ref[0, t0 + j], axis=0, keepdims=True)
            y = jnp.where(rows == j, yj, y)
        z = z_ref[0, pl.ds(t0, GROUP), :].astype(jnp.float32)
        o = (y + x * dk) * (z * jax.nn.sigmoid(z))
        o_ref[0, pl.ds(t0, GROUP), :] = o.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // GROUP, group, h_ref[...])

    @pl.when(pl.program_id(2) == n_chunks - 1)
    def _store_state():
        hout_ref[0] = h_ref[...]


def scan_gate(dt: jnp.ndarray, A: jnp.ndarray, B: jnp.ndarray,
              c: jnp.ndarray, x_skip: jnp.ndarray, d_skip: jnp.ndarray,
              z: jnp.ndarray, h0: Optional[jnp.ndarray] = None,
              d_block: Optional[int] = None, chunk: Optional[int] = None,
              interpret: Optional[bool] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """dt: (b, s, di) Δ after softplus; A: (di, st) = -exp(a_log);
    B, c: (b, s, st); x_skip, z: (b, s, di); d_skip: (di,); h0: (b, di,
    st) or None (zeros).  Δ, A, B, c, d_skip and the state are taken in
    f32.  Returns (o (b, s, di), h_last (b, di, st) f32)."""
    bsz, seq, di = dt.shape
    st = A.shape[1]
    if d_block is None or chunk is None:
        from ..core.akg import plan_scan_gate
        plan = plan_scan_gate(seq, di, st)
        d_block = d_block if d_block is not None else plan.tile["d"]
        chunk = chunk if chunk is not None else plan.tile["t"]
    seq_p, d_block, chunk = block_geometry(seq, di, d_block, chunk)
    # padded steps have Δ = 0: a = 1, b = 0, which leave the state exact
    pad = ((0, 0), (0, seq_p - seq), (0, 0))
    dt, x_skip, z = (jnp.pad(v, pad) for v in
                     (dt.astype(jnp.float32), x_skip, z))
    B, c = (jnp.pad(v.astype(jnp.float32), pad)[..., None] for v in (B, c))
    if h0 is None:
        h0 = jnp.zeros((bsz, di, st), jnp.float32)
    h0 = jnp.swapaxes(h0.astype(jnp.float32), 1, 2)          # (b, st, di)
    n_chunks = seq_p // chunk
    grid = (bsz, di // d_block, n_chunks)
    row = pl.BlockSpec((1, chunk, d_block), lambda i, d, t: (i, t, d))
    col = pl.BlockSpec((1, chunk, st, 1), lambda i, d, t: (i, t, 0, 0))
    state = pl.BlockSpec((1, st, d_block), lambda i, d, t: (i, 0, d))
    out, h_last = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            row,
            pl.BlockSpec((st, d_block), lambda i, d, t: (0, d)),
            col,
            col,
            row,
            pl.BlockSpec((1, d_block), lambda i, d, t: (0, d)),
            row,
            state,
        ],
        out_specs=[row, state],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, seq_p, di), x_skip.dtype),
            jax.ShapeDtypeStruct((bsz, st, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((st, d_block), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="scan_gate",
    )(dt, jnp.swapaxes(A.astype(jnp.float32), 0, 1), B, c, x_skip,
      d_skip.astype(jnp.float32).reshape(1, di), z, h0)
    return out[:, :seq], jnp.swapaxes(h_last, 1, 2)
