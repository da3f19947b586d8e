"""Mamba-1 block (selective SSM) — falcon-mamba / jamba layers.

Sequence processing uses an associative scan over the diagonal SSM
recurrence h_t = a_t ⊙ h_{t-1} + b_t (a_t = exp(Δ_t·A)), which is both
TPU-friendly (log-depth) and exact. Decode keeps (conv_state, ssm_state)
as the cache.  Prefill, chunked prefill and decode share one set of
input projections (``_ssm_inputs``, named scope ``ssm_inputs``), which
holds Falcon-Mamba's B/C/Δ norm where the config sets ``bcdt_rms_eps``.
The fused Pallas route hands Δ, A, B and C to ``scan_gate``, which
discretises inside the kernel; the jnp routes discretise with
``discretise``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.registry import ArchConfig
from .layers import dense_init, rmsnorm
from .sharding import shard_activation


def init_mamba(key, cfg: ArchConfig, dtype) -> Dict:
    d, di, st, dtr = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, di), jnp.float32) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "x_proj": dense_init(ks[2], di, dtr + 2 * st, dtype),
        "dt_proj": dense_init(ks[3], dtr, di, dtype),
        "dt_bias": jnp.zeros((di,), jnp.float32),
        "a_log": jnp.log(jnp.broadcast_to(jnp.arange(1, st + 1, dtype=jnp.float32), (di, st))),
        "d_skip": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(ks[4], di, d, dtype),
    }


def _ssm_scan(a, b):
    """Associative scan over (decay, increment) pairs along axis 1."""
    def combine(x, y):
        ax, bx = x
        ay, by = y
        return ax * ay, bx * ay + by
    return jax.lax.associative_scan(combine, (a, b), axis=1)


@jax.named_scope("ssm_inputs")
def _ssm_inputs(p, cfg: ArchConfig, xs):
    """Input-dependent recurrence inputs from post-conv activations xs
    (..., di), all f32: (Δ (..., di), A (di, st), Bm, Cm (..., st)).
    With ``cfg.bcdt_rms_eps`` set, Δ's low-rank input, B and C each go
    through a weight-free RMSNorm first (Falcon-Mamba)."""
    st, dtr = cfg.ssm_state, cfg.dt_rank_
    proj = xs @ p["x_proj"]                                     # (..., dtr+2st)
    dt_r, Bm, Cm = jnp.split(proj.astype(jnp.float32), [dtr, dtr + st], axis=-1)
    if cfg.bcdt_rms_eps:
        dt_r, Bm, Cm = (rmsnorm(v, jnp.zeros(()), cfg.bcdt_rms_eps)
                          for v in (dt_r, Bm, Cm))
    dt = jax.nn.softplus(dt_r @ p["dt_proj"].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["a_log"])                                    # (di, st)
    return dt, A, Bm, Cm


def discretise(dt, A, Bm, xs):
    """The recurrence's coefficients a_bar = exp(Δ·A) and b_bar = Δ·B·x,
    each (..., di, st) f32, from ``_ssm_inputs``' Δ, A and B and xs."""
    a_bar = jnp.exp(dt[..., None] * A)
    b_bar = (dt[..., None] * Bm[..., None, :]) * xs.astype(jnp.float32)[..., None]
    return a_bar, b_bar


def _fused_scan_gate(cfg: ArchConfig, xs) -> bool:
    from .pallas_mode import mode
    md = mode()
    return (md.enabled and md.fused_scan_gate
            and xs.shape[1] >= md.min_scan_seq)


def _selective_ssm(p, cfg: ArchConfig, xs, return_last: bool = False):
    """xs: (b, s, di) post-conv activations; returns ((b, s, di), h_last)."""
    dt, A, Bm, Cm = _ssm_inputs(p, cfg, xs)
    _, h = _ssm_scan(*discretise(dt, A, Bm, xs))                # (b, s, di, st)
    y = jnp.einsum("bsdn,bsn->bsd", h, Cm)
    y = y + xs.astype(jnp.float32) * p["d_skip"]
    return y.astype(xs.dtype), (h[:, -1] if return_last else None)


@jax.named_scope("ssm")
def mamba(p, cfg: ArchConfig, x, return_state: bool = False):
    """Full-sequence Mamba block. x: (b, s, d)."""
    di = cfg.d_inner
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, [di], axis=-1)
    xs = shard_activation(xs, ("batch", "seq", "ffn"))
    # causal depthwise conv
    w = p["conv_w"].astype(jnp.float32)                        # (cw, di)
    cw = w.shape[0]
    pre_conv = xs
    pad = jnp.pad(xs.astype(jnp.float32), ((0, 0), (cw - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + xs.shape[1], :] * w[i] for i in range(cw))
    xs = jax.nn.silu(conv + p["conv_b"]).astype(x.dtype)
    if _fused_scan_gate(cfg, xs):
        from ..kernels import ops
        dt, A, Bm, Cm = _ssm_inputs(p, cfg, xs)
        y, h_full = ops.scan_gate(dt, A, Bm, Cm, xs, p["d_skip"], z)
        h_last = h_full if return_state else None
    else:
        y, h_last = _selective_ssm(p, cfg, xs, return_last=return_state)
        y = y * jax.nn.silu(z)
    y = shard_activation(y, ("batch", "seq", "ffn"))
    out = y @ p["out_proj"]
    if return_state:
        conv_state = pre_conv[:, -(cw - 1):, :]
        return out, (conv_state, h_last)
    return out


def init_mamba_cache(cfg: ArchConfig, batch: int, layer_count: int, dtype) -> Dict:
    di = cfg.d_inner
    return {
        "conv": jnp.zeros((layer_count, batch, cfg.conv_width - 1, di), dtype),
        "ssm": jnp.zeros((layer_count, batch, di, cfg.ssm_state), jnp.float32),
    }


@jax.named_scope("ssm")
def mamba_decode(p, cfg: ArchConfig, x, conv_state, ssm_state
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode. x: (b, 1, d); conv_state: (b, cw-1, di);
    ssm_state: (b, di, st)."""
    di = cfg.d_inner
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, [di], axis=-1)                       # (b, 1, di)
    w = p["conv_w"].astype(jnp.float32)
    hist = jnp.concatenate([conv_state.astype(jnp.float32),
                            xs.astype(jnp.float32)], axis=1)    # (b, cw, di)
    conv = jnp.einsum("bcd,cd->bd", hist, w) + p["conv_b"]
    xs1 = jax.nn.silu(conv).astype(x.dtype)                    # (b, di)
    dt, A, Bm, Cm = _ssm_inputs(p, cfg, xs1)
    a_bar, b_bar = discretise(dt, A, Bm, xs1)                  # (b, di, st)
    h = ssm_state * a_bar + b_bar
    y = jnp.einsum("bdn,bn->bd", h, Cm) + xs1.astype(jnp.float32) * p["d_skip"]
    y = (y.astype(x.dtype) * jax.nn.silu(z[:, 0]))[:, None, :]
    out = y @ p["out_proj"]
    return out, hist[:, 1:].astype(conv_state.dtype), h


@jax.named_scope("ssm")
def mamba_chunk(p, cfg: ArchConfig, x, conv_state, ssm_state
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked-prefill Mamba with explicit state carry: x (b, c, d) is a
    contiguous chunk of the sequence; conv_state (b, cw-1, di) and
    ssm_state (b, di, st) carry the causal conv tail and hidden state
    from the previous chunk.  The fused Pallas route hands ``ssm_state``
    to the scan+gate kernel's ``h0``; the jnp route folds it in through
    the associative scan's cumulative decay.  Returns
    (out, new_conv_state, h_last)."""
    di = cfg.d_inner
    c = x.shape[1]
    xz = x @ p["in_proj"]
    xs, z = jnp.split(xz, [di], axis=-1)                       # (b, c, di)
    w = p["conv_w"].astype(jnp.float32)
    cw = w.shape[0]
    pre = jnp.concatenate([conv_state, xs], axis=1)            # (b, cw-1+c, di)
    hist = pre.astype(jnp.float32)
    conv = sum(hist[:, i:i + c, :] * w[i] for i in range(cw))
    new_conv = pre[:, -(cw - 1):, :] if cw > 1 else conv_state
    xs = jax.nn.silu(conv + p["conv_b"]).astype(x.dtype)
    dt, A, Bm, Cm = _ssm_inputs(p, cfg, xs)
    if _fused_scan_gate(cfg, xs):
        from ..kernels import ops
        y, h_last = ops.scan_gate(dt, A, Bm, Cm, xs, p["d_skip"], z,
                                  h0=ssm_state)
    else:
        cum_a, h = _ssm_scan(*discretise(dt, A, Bm, xs))
        h = h + cum_a * ssm_state.astype(jnp.float32)[:, None]
        y = jnp.einsum("bsdn,bsn->bsd", h, Cm)
        y = (y + xs.astype(jnp.float32) * p["d_skip"]).astype(xs.dtype)
        y = y * jax.nn.silu(z)
        h_last = h[:, -1]
    out = y @ p["out_proj"]
    return out, new_conv, h_last
