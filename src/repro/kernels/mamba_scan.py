"""Selective-scan (Mamba-1) recurrence Pallas kernel.

h_t = a_t ⊙ h_{t-1} + b_t over the sequence, with the hidden state
(state × d_block) resident in VMEM scratch across sequence chunks:
grid = (batch, d_blocks, seq_chunks), the chunk axis minormost.

TPU layout: d_inner rides the 128 lanes and the state dim the sublanes,
so a/b blocks are (chunk, state, d_block) and the time step indexes a
major axis.  Per-step outputs are one row each; they are gathered into
groups of ``GROUP`` (= 8 sublanes) rows and stored with one aligned
write, so no load or store sits at a dynamic, unaligned sublane offset.
The wrappers take the model's (b, s, d_inner, state) layout, transpose
to the kernel's, and pad the sequence to a multiple of ``GROUP`` with
identity steps (a = 1, b = 0), which leave the state exact.

Block geometry comes from the scheduler: ``repro.core.akg.plan_mamba_scan``
schedules the recurrence SCoP (t sequential-outermost by the h
dependence, d/n parallel inside) and lowers its schedule tree to a
KernelPlan — chunk = the t tile, d_block = the d tile — through the
same ``lower_to_kernel_plan`` path as matmul and flash attention.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._mode import resolve_interpret

GROUP = 8


def scan_chunk(a_ref, b_ref, c_ref, h, chunk: int, emit):
    """Run the recurrence over one chunk of blocks ``a_ref``/``b_ref``
    (1, chunk, st, bd) and ``c_ref`` (1, chunk, st, 1) from state ``h``
    (st, bd) f32.  Every ``GROUP`` steps, ``emit(t0, y)`` receives the
    rows y[t0:t0+GROUP] = Σ_n h_t[n]·c_t[n] as one (GROUP, bd) array.
    Returns the final state."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (GROUP, h.shape[1]), 0)

    def group(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        y = jnp.zeros((GROUP, h.shape[1]), jnp.float32)
        for j in range(GROUP):
            h = (a_ref[0, t0 + j].astype(jnp.float32) * h
                 + b_ref[0, t0 + j].astype(jnp.float32))
            yj = jnp.sum(h * c_ref[0, t0 + j].astype(jnp.float32), axis=0,
                         keepdims=True)                      # (1, bd)
            y = jnp.where(rows == j, yj, y)
        emit(t0, y)
        return h

    return jax.lax.fori_loop(0, chunk // GROUP, group, h)


def to_kernel_layout(a_bar, b_bar, c, seq_pad: int):
    """(b, s, di, st) a/b and (b, s, st) c → the kernel's (b, s', st, di)
    and (b, s', st, 1), padded with ``seq_pad`` identity steps."""
    a = jnp.swapaxes(a_bar, 2, 3)
    b = jnp.swapaxes(b_bar, 2, 3)
    c = c[..., None]
    if seq_pad:
        pad = ((0, 0), (0, seq_pad), (0, 0), (0, 0))
        a = jnp.pad(a, pad, constant_values=1)
        b = jnp.pad(b, pad)
        c = jnp.pad(c, pad)
    return a, b, c


def block_geometry(seq: int, di: int, d_block: int, chunk: int
                   ) -> Tuple[int, int, int]:
    """(padded seq, d_block, chunk): seq padded to a GROUP multiple,
    d_block dividing di, chunk a GROUP multiple dividing the padded seq."""
    seq_p = -(-seq // GROUP) * GROUP
    d_block = min(d_block, di)
    while di % d_block:
        d_block //= 2
    chunk = max(GROUP, min(chunk, seq_p) // GROUP * GROUP)
    while seq_p % chunk:
        chunk -= GROUP
    return seq_p, d_block, chunk


def _kernel(a_ref, b_ref, c_ref, o_ref, h_ref, *, chunk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def emit(t0, y):
        o_ref[0, pl.ds(t0, GROUP), :] = y.astype(o_ref.dtype)

    h_ref[...] = scan_chunk(a_ref, b_ref, c_ref, h_ref[...], chunk, emit)


def selective_scan(a_bar: jnp.ndarray, b_bar: jnp.ndarray, c: jnp.ndarray,
                   d_block: Optional[int] = None, chunk: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """a_bar, b_bar: (batch, seq, d_inner, state); c: (batch, seq, state).
    Returns y: (batch, seq, d_inner) = Σ_n h[., ., d, n]·c[., ., n].
    Default block geometry comes from the PolyTOPS schedule tree."""
    bsz, seq, di, st = a_bar.shape
    if d_block is None or chunk is None:
        from ..core.akg import plan_mamba_scan
        plan = plan_mamba_scan(seq, di, st)
        d_block = d_block if d_block is not None else plan.tile["d"]
        chunk = chunk if chunk is not None else plan.tile["t"]
    seq_p, d_block, chunk = block_geometry(seq, di, d_block, chunk)
    a, b, c4 = to_kernel_layout(a_bar, b_bar, c, seq_p - seq)
    grid = (bsz, di // d_block, seq_p // chunk)
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, st, d_block), lambda i, d, t: (i, t, 0, d)),
            pl.BlockSpec((1, chunk, st, d_block), lambda i, d, t: (i, t, 0, d)),
            pl.BlockSpec((1, chunk, st, 1), lambda i, d, t: (i, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block), lambda i, d, t: (i, t, d)),
        out_shape=jax.ShapeDtypeStruct((bsz, seq_p, di), a_bar.dtype),
        scratch_shapes=[pltpu.VMEM((st, d_block), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="selective_scan",
    )(a, b, c4)
    return out[:, :seq]
