"""Building blocks of the plain float32 references, and the precision
they compute in.

A reference runs one layer at a time over a few whole sequences, each
padded at its end to one length (a causal model's earlier positions
never see the padding), so every layer reuses one compiled program and
at most one layer's weights are live besides the activations.  Matrix
products run under ``default_matmul_precision("highest")``: on a TPU a
float32 product is otherwise made of bfloat16 passes.

``fp8`` is the control: the same computation with both operands of every
matrix product rounded to float8 e4m3, scaled per row of the activations
and per column of the weights — the step below the configuration's
bfloat16 that a later change might take.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_E4M3_MAX = 448.0


def fp8_round(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the contraction axis), returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(a: jnp.ndarray, w: jnp.ndarray, precision: str) -> jnp.ndarray:
    """``a @ w`` (a: (..., K), w: (K, N)) in the given precision."""
    a = a.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        a, w = fp8_round(a, -1), fp8_round(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def causal_attention(q, k, v, scale: float, precision: str) -> jnp.ndarray:
    """Causal grouped-query attention over one sequence.
    q: (s, heads, hd); k, v: (s, kv_heads, hd) -> (s, heads, hd)."""
    s, h, hd = q.shape
    g = k.shape[1]
    q = q.astype(jnp.float32).reshape(s, g, h // g, hd)
    k, v = k.astype(jnp.float32), v.astype(jnp.float32)
    if precision == "fp8":
        q, k = fp8_round(q, -1), fp8_round(k, -1)
    hi = jax.lax.Precision.HIGHEST
    scores = jnp.einsum("qgrd,kgd->grqk", q, k, precision=hi) * scale
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    if precision == "fp8":
        probs, v = fp8_round(probs, -1), fp8_round(v, 0)
    out = jnp.einsum("grqk,kgd->qgrd", probs, v, precision=hi)
    return out.reshape(s, h, hd)


def rmsnorm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding, rotate-half layout.  x: (s, heads, hd)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def silu(x):
    return x * jax.nn.sigmoid(x)
