"""Fused selective-scan + skip + SiLU-gate Pallas kernel (Mamba block tail).

One kernel computes what the jnp path spreads over four ops:

    h_t = a_t ⊙ h_{t-1} + b_t                      (recurrence)
    y_t = h_t · c_t + x_t ⊙ d_skip                 (contraction + skip)
    o_t = y_t ⊙ silu(z_t)                          (gate)

with the hidden state (state × d_block) VMEM-resident across sequence
chunks and an explicit initial state ``h0`` — the carry that lets a
serving engine process a prompt in chunks (continuous batching) without
ever materializing the (b, s, d, n) hidden-state tensor in HBM between
ops.  The final state is returned for the next chunk.  The recurrence
and the TPU layout (d on lanes, state on sublanes, aligned row groups)
are shared with :mod:`.mamba_scan`.

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
from .mamba_scan import GROUP, block_geometry, scan_chunk, to_kernel_layout


def _kernel(a_ref, b_ref, c_ref, x_ref, dk_ref, z_ref, h0_ref,
            o_ref, hout_ref, h_ref, *, chunk: int, n_chunks: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    dk = dk_ref[...].astype(jnp.float32)                 # (1, bd)

    def emit(t0, y):
        x = x_ref[0, pl.ds(t0, GROUP), :].astype(jnp.float32)
        z = z_ref[0, pl.ds(t0, GROUP), :].astype(jnp.float32)
        o = (y + x * dk) * (z * jax.nn.sigmoid(z))
        o_ref[0, pl.ds(t0, GROUP), :] = o.astype(o_ref.dtype)

    h_ref[...] = scan_chunk(a_ref, b_ref, c_ref, h_ref[...], chunk, emit)

    @pl.when(pl.program_id(2) == n_chunks - 1)
    def _store_state():
        hout_ref[0] = h_ref[...]


def scan_gate(a_bar: jnp.ndarray, b_bar: jnp.ndarray, c: jnp.ndarray,
              x_skip: jnp.ndarray, d_skip: jnp.ndarray, z: jnp.ndarray,
              h0: Optional[jnp.ndarray] = None,
              d_block: Optional[int] = None, chunk: Optional[int] = None,
              interpret: Optional[bool] = None
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """a_bar, b_bar: (b, s, di, st); c: (b, s, st); x_skip, z: (b, s, di);
    d_skip: (di,); h0: (b, di, st) f32 or None (zeros).
    Returns (o (b, s, di), h_last (b, di, st) f32)."""
    bsz, seq, di, st = a_bar.shape
    if d_block is None or chunk is None:
        from ..core.akg import plan_scan_gate
        plan = plan_scan_gate(seq, di, st)
        d_block = d_block if d_block is not None else plan.tile["d"]
        chunk = chunk if chunk is not None else plan.tile["t"]
    seq_p, d_block, chunk = block_geometry(seq, di, d_block, chunk)
    a, b, c4 = to_kernel_layout(a_bar, b_bar, c, seq_p - seq)
    pad = ((0, 0), (0, seq_p - seq), (0, 0))
    x_skip, z = jnp.pad(x_skip, pad), jnp.pad(z, pad)
    if h0 is None:
        h0 = jnp.zeros((bsz, di, st), jnp.float32)
    h0 = jnp.swapaxes(h0.astype(jnp.float32), 1, 2)          # (b, st, di)
    n_chunks = seq_p // chunk
    grid = (bsz, di // d_block, n_chunks)
    row = pl.BlockSpec((1, chunk, d_block), lambda i, d, t: (i, t, d))
    state = pl.BlockSpec((1, st, d_block), lambda i, d, t: (i, 0, d))
    out, h_last = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, st, d_block), lambda i, d, t: (i, t, 0, d)),
            pl.BlockSpec((1, chunk, st, d_block), lambda i, d, t: (i, t, 0, d)),
            pl.BlockSpec((1, chunk, st, 1), lambda i, d, t: (i, t, 0, 0)),
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
    )(a, b, c4, x_skip, d_skip.reshape(1, di), z, h0)
    return out[:, :seq], jnp.swapaxes(h_last, 1, 2)
