"""GQA attention: full / sliding-window / cross, with KV-cache decode.

Activation shardings are annotated with ``with_sharding_constraint``
using logical axis names resolved by the caller-installed mesh rules
(see repro.launch.mesh.logical_axis_rules); under a plain jit (smoke
tests) the constraints are no-ops.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.registry import ArchConfig
from .layers import apply_mrope, apply_rope, dense_init, rmsnorm, rmsnorm_init
from .sharding import shard_activation

NEG_INF = -2.3819763e38

# q-chunked attention (flash-style memory behaviour without a custom
# kernel): when > 0 and seq divides, attention computes q in chunks via
# lax.map with per-chunk rematerialization, bounding the live logits to
# (batch, heads, chunk, seq_kv). Installed by the launcher for long-seq
# shapes; 0 = full materialization (baseline).
ATTN_CHUNK = 0


def _pallas():
    from .pallas_mode import mode
    return mode()


def _flash(q, k, v, q_offset=None):
    from ..kernels import ops
    return ops.flash_attention(q, k, v, causal=True, q_offset=q_offset)


def init_attention(key, cfg: ArchConfig, dtype) -> Dict:
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd)
        p["k_norm"] = rmsnorm_init(hd)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _qkv(p, cfg: ArchConfig, x, positions, mrope_positions=None):
    hd = cfg.hd
    q = _split_heads(x @ p["wq"], cfg.n_heads, hd)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if cfg.mrope and mrope_positions is not None:
        q = apply_mrope(q, mrope_positions)
        k = apply_mrope(k, mrope_positions)
    else:
        q = apply_rope(q, positions)
        k = apply_rope(k, positions)
    q = shard_activation(q, ("batch", "seq", "heads", None))
    k = shard_activation(k, ("batch", "seq", "kv_heads", None))
    return q, k, v


def _sdpa(q, k, v, mask, n_rep: int):
    """q: (b, sq, h, d); k/v: (b, skv, hkv, d); mask: (b, sq, skv) or None."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    qf = q.astype(jnp.float32) / math.sqrt(d)
    q_g = qf.reshape(b, sq, hkv, n_rep, d)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", q_g, k.astype(jnp.float32))
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(v.dtype)


def _sdpa_chunked(q, k, v, n_rep: int, window: int, chunk: int,
                  causal: bool = True):
    """Map over q chunks; per-chunk remat keeps only (q,k,v) live."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    nc = sq // chunk
    qr = q.reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def one(args):
        qc, ci = args
        rows = ci * chunk + jnp.arange(chunk)[:, None]
        cols = jnp.arange(skv)[None, :]
        m = rows >= cols if causal else jnp.ones((chunk, skv), bool)
        if window:
            m &= (rows - cols) < window
        mask = jnp.broadcast_to(m[None], (b, chunk, skv))
        return _sdpa(qc, k, v, mask, n_rep)

    out = jax.lax.map(jax.checkpoint(one),
                      (qr, jnp.arange(nc, dtype=jnp.int32)))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, d)


def causal_mask(sq: int, window: int = 0) -> jnp.ndarray:
    i = jnp.arange(sq)[:, None]
    j = jnp.arange(sq)[None, :]
    m = j <= i
    if window:
        m &= (i - j) < window
    return m[None]   # (1, sq, sq)


@jax.named_scope("attention")
def attention(p, cfg: ArchConfig, x, positions, *, window: int = 0,
              mrope_positions=None, return_kv: bool = False):
    """Training/prefill self-attention (causal, optional sliding window)."""
    q, k, v = _qkv(p, cfg, x, positions, mrope_positions)
    sq = x.shape[1]
    n_rep = cfg.n_heads // cfg.n_kv_heads
    md = _pallas()
    if md.enabled and window == 0 and sq >= md.min_attn_q:
        out = _flash(q, k, v)
    elif ATTN_CHUNK and sq > ATTN_CHUNK and sq % ATTN_CHUNK == 0:
        out = _sdpa_chunked(q, k, v, n_rep, window, ATTN_CHUNK)
    else:
        out = _sdpa(q, k, v, causal_mask(sq, window), n_rep)
    out = out.reshape(x.shape[0], x.shape[1], -1)
    out = out @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


@jax.named_scope("attention")
def attention_noncausal(p, cfg: ArchConfig, x, positions) -> jnp.ndarray:
    """Encoder self-attention (bidirectional)."""
    q, k, v = _qkv(p, cfg, x, positions)
    out = _sdpa(q, k, v, None, cfg.n_heads // cfg.n_kv_heads)
    return out.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]


@jax.named_scope("attention")
def cross_attention(p, cfg: ArchConfig, x, memory, positions) -> jnp.ndarray:
    """Decoder cross-attention over encoder memory (no rope on memory)."""
    hd = cfg.hd
    q = _split_heads(x @ p["wq"], cfg.n_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    q = apply_rope(q, positions)
    k = _split_heads(memory @ p["wk"], cfg.n_kv_heads, hd)
    v = _split_heads(memory @ p["wv"], cfg.n_kv_heads, hd)
    out = _sdpa(q, k, v, None, cfg.n_heads // cfg.n_kv_heads)
    return out.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]


# ---------------------------------------------------------------------------
# decode with KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ArchConfig, batch: int, max_len: int, layer_count: int,
                  dtype) -> Dict:
    hd = cfg.hd
    shape = (layer_count, batch, max_len, cfg.n_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@jax.named_scope("attention")
def decode_attention(p, cfg: ArchConfig, x, k_cache, v_cache, cache_len,
                     *, window: int = 0, mrope_positions=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token decode: x (b, 1, d); k/v_cache (b, S, hkv, hd) hold
    `cache_len` valid entries; returns (out, new_k_entry, new_v_entry)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), cache_len, jnp.int32)
    q, k_new, v_new = _qkv(p, cfg, x, positions, mrope_positions)
    k_all = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, cache_len, axis=1)
    v_all = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, cache_len, axis=1)
    S = k_all.shape[1]
    j = jnp.arange(S)[None, None, :]
    mask = j <= cache_len
    if window:
        mask &= j > (cache_len - window)
    out = _sdpa(q, k_all, v_all, jnp.broadcast_to(mask, (b, 1, S)),
                cfg.n_heads // cfg.n_kv_heads)
    out = out.reshape(b, 1, -1) @ p["wo"]
    return out, k_all, v_all


# ---------------------------------------------------------------------------
# serving fast path: chunked prefill + ragged paged decode
# ---------------------------------------------------------------------------

@jax.named_scope("attention")
def chunk_attention(p, cfg: ArchConfig, x, k_prefix, v_prefix, offset, *,
                    window: int = 0
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Chunked-prefill self-attention: x (b, c, d) holds rows
    ``[offset, offset+c)`` of the sequence (``offset`` a traced scalar);
    ``k_prefix``/``v_prefix`` (b, kv_len, hkv, hd) are the cache's first
    ``kv_len`` rows — the static page-aligned prefix covering
    ``offset + c``, all rows < offset already prefilled.  The chunk's
    k/v are put into the prefix at ``offset`` and attention runs
    causally over it (unwritten rows beyond the diagonal are masked, so
    the page bound is exact).  The Pallas route uses the flash kernel's
    SMEM ``q_offset``: one compiled kernel serves every chunk position.
    Returns (out, k_rows, v_rows): the chunk's own (b, c, hkv, hd) rows,
    in the cache's dtype, for the caller to write at ``offset``."""
    b, c, _ = x.shape
    kv_len = k_prefix.shape[1]
    positions = jnp.broadcast_to(offset + jnp.arange(c)[None, :], (b, c))
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    k_new = k_new.astype(k_prefix.dtype)
    v_new = v_new.astype(v_prefix.dtype)
    kp = jax.lax.dynamic_update_slice_in_dim(k_prefix, k_new, offset, axis=1)
    vp = jax.lax.dynamic_update_slice_in_dim(v_prefix, v_new, offset, axis=1)
    md = _pallas()
    if md.enabled and window == 0 and c >= md.min_attn_q:
        out = _flash(q, kp, vp, q_offset=offset)
    else:
        rows = offset + jnp.arange(c)[:, None]
        cols = jnp.arange(kv_len)[None, :]
        m = rows >= cols
        if window:
            m &= (rows - cols) < window
        out = _sdpa(q, kp, vp, jnp.broadcast_to(m[None], (b, c, kv_len)),
                    cfg.n_heads // cfg.n_kv_heads)
    out = out.reshape(b, c, -1) @ p["wo"]
    return out, k_new, v_new


@jax.named_scope("attention")
def paged_decode_attention(p, cfg: ArchConfig, x, k_prefix, v_prefix,
                           lengths, *, window: int = 0
                           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Ragged one-token decode over a page-aligned KV prefix.

    x: (b, 1, d); ``k_prefix``/``v_prefix`` (b, kv_len, hkv, hd): the
    cache's first ``kv_len`` rows (static, page-aligned); lengths: (b,)
    int32 per-slot valid lengths (each slot's token goes at its own
    ``lengths[i]`` — no shared ``max(lengths)`` that would expose stale
    rows in shorter slots).  Bit-identical to :func:`decode_attention`
    over the full cache — masked entries contribute exact zeros to the
    softmax — while moving only the used pages.  Returns (out, k_row,
    v_row): each slot's own (b, 1, hkv, hd) row, in the cache's dtype,
    for the caller to write at ``lengths``."""
    b = x.shape[0]
    kv_len = k_prefix.shape[1]
    positions = lengths[:, None].astype(jnp.int32)
    q, k_new, v_new = _qkv(p, cfg, x, positions)
    k_new = k_new.astype(k_prefix.dtype)
    v_new = v_new.astype(v_prefix.dtype)
    # the new row enters the prefix by a select, which fuses into the
    # read; a slot whose row lies past the prefix (an inactive slot
    # beyond the page bound) leaves the prefix as it is
    j = jnp.arange(kv_len)[None, None, :]
    at = (j[0] == lengths[:, None])[:, :, None, None]
    kp = jnp.where(at, k_new, k_prefix)
    vp = jnp.where(at, v_new, v_prefix)
    mask = j <= lengths[:, None, None]
    if window:
        mask &= j > (lengths[:, None, None] - window)
    out = _sdpa(q, kp, vp, jnp.broadcast_to(mask, (b, 1, kv_len)),
                cfg.n_heads // cfg.n_kv_heads)
    out = out.reshape(b, 1, -1) @ p["wo"]
    return out, k_new, v_new
