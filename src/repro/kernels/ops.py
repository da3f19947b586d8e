"""jit'd public wrappers for the Pallas kernels.

``interpret=None`` (the default) lets the backend choose: Mosaic
compiles the kernels on a TPU, and the Pallas interpreter runs the
kernel bodies on the CPU, for correctness checks
(:func:`repro.kernels._mode.resolve_interpret`).  Any other backend is
an error.  Pass ``True`` or ``False`` to force a mode.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import mamba_scan as _ms
from . import matmul_polytops as _mm
from . import scan_gate as _sg


@partial(jax.jit, static_argnames=("interpret",))
def matmul(a, b, interpret: Optional[bool] = None):
    return _mm.matmul(a, b, interpret=interpret)


@partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, causal: bool = True, q_offset=None,
                    interpret: Optional[bool] = None):
    """q: (b, s, h, d); k/v: (b, s, hkv, d) — GQA repeats kv heads.
    ``q_offset`` (scalar int32) positions the q chunk for causal
    masking against a longer kv prefix (chunked prefill)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    out = _fa.flash_attention(qf, kf, vf, causal=causal, q_offset=q_offset,
                              interpret=interpret)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("interpret",))
def selective_scan(a_bar, b_bar, c, interpret: Optional[bool] = None):
    return _ms.selective_scan(a_bar, b_bar, c, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def scan_gate(dt, A, B, c, x_skip, d_skip, z, h0=None,
              interpret: Optional[bool] = None):
    """Fused discretisation + selective scan + skip + SiLU gate with
    state carry, from Δ, A, B and C.
    Returns (o (b, s, di), h_last (b, di, st))."""
    return _sg.scan_gate(dt, A, B, c, x_skip, d_skip, z, h0=h0,
                         interpret=interpret)
