"""Causal flash attention Pallas kernel (online softmax).

Block geometry from repro.core.akg.plan_attention (PolyTOPS schedules
the QKᵀ core: head_dim → lanes, q/k block band → grid). Grid is
(batch·heads, q_blocks, k_blocks) with the k axis minormost; the running
(max, sum, acc) state lives in VMEM scratch across k blocks. Causality
is handled by masking within the diagonal block and by pl.when-skipping
blocks above the diagonal.

``q_offset`` supports chunked prefill: the q rows are a contiguous
chunk starting at that (traced, scalar) position of the sequence, so
causality masks against ``q_offset + row`` — one compiled kernel serves
every chunk position.  The offset rides in SMEM; 0 recovers the plain
causal kernel bit-for-bit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.akg import plan_attention
from ._mode import resolve_interpret

NEG_INF = -1e30


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, bq: int, bk: int, k_steps: int, scale: float, causal: bool):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    off = off_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _block():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        s = q @ k.T                                       # (bq, bk)
        if causal:
            rows = off + qi * bq \
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + p @ v_ref[0].astype(jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks entirely above the (offset) diagonal
        pl.when(off + qi * bq + bq - 1 >= ki * bk)(_block)
    else:
        _block()

    @pl.when(ki == k_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    q_offset: Optional[jnp.ndarray] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q, k, v: (bh, seq, d) — batch×heads flattened. GQA repetition is
    handled by the ops wrapper.  ``q_offset`` (scalar int32, traced)
    places the q rows at that sequence position for causal masking —
    the chunked-prefill case where k holds ``q_offset + sq`` (or more,
    trailing rows masked out by causality) valid entries."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    plan = plan_attention(sq, sk, d)
    bq = min(block_q or plan.tile.get("q", 128), sq)
    bk = min(block_k or plan.tile.get("kk", 128), sk)
    while sq % bq:
        bq //= 2
    while sk % bk:
        bk //= 2
    k_steps = sk // bk
    grid = (bh, sq // bq, k_steps)
    scale = 1.0 / (d ** 0.5)
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    off = jnp.asarray(q_offset, jnp.int32).reshape((1,))
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, k_steps=k_steps,
                          scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="flash_attention",
    )(off, q, k, v)
