"""Model assembly: decoder stacks (dense/MoE/SSM/hybrid), encoder-decoder,
VLM/audio frontends (stubs per brief), train/prefill/decode entry points.

Layers are grouped into repeating *pattern blocks* (e.g. jamba's
8-layer mamba×7+attn block, gemma3's 5 local + 1 global) and executed
with ``lax.scan`` over stacked parameters — one block of HLO regardless
of depth, which keeps the 512-device dry-run compilable on one host.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.registry import ArchConfig
from . import attention as ATT
from . import mlp as MLP
from . import ssm as SSM
from .layers import dtype_of, embed, embed_init, rmsnorm, rmsnorm_init, unembed
from .sharding import gather_params_for_compute, shard_activation


# When True, layer stacks run as unrolled Python loops instead of
# lax.scan — used by the dry-run cost probes (XLA's cost_analysis counts
# a while body once regardless of trip count, so probes must unroll).
UNROLL = False

# Activation checkpointing policy for the layer stack ('none' | 'full' |
# 'dots'). 'full' recomputes the whole block in backward (only the
# inter-block carry is saved) — without it a scanned stack saves every
# attention matrix for backward (O(layers·seq²) — 49 GiB/device for
# qwen2-vl train_4k). 'dots' saves matmul outputs (less recompute, more
# memory) — a §Perf hillclimbing knob.
REMAT = "full"


def _maybe_remat(fn):
    if REMAT == "none":
        return fn
    if REMAT == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    return jax.checkpoint(fn, prevent_cse=False)


@dataclass(frozen=True)
class LayerSpec:
    mixer: str          # 'attn' | 'mamba' | 'enc_attn'
    window: int         # sliding window (0 = full)
    ffn: str            # 'mlp' | 'moe' | 'none'
    cross: bool = False


def layer_specs(cfg: ArchConfig, role: str = "decoder") -> List[LayerSpec]:
    n = cfg.enc_layers if role == "encoder" else cfg.n_layers
    specs = []
    for i in range(n):
        if role == "encoder":
            specs.append(LayerSpec("enc_attn", 0, "mlp"))
            continue
        if cfg.family == "ssm":
            specs.append(LayerSpec("mamba", 0, "none"))
            continue
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        window = 0
        if cfg.sliding_window and not cfg.is_global_attn_layer(i):
            window = cfg.sliding_window
        ffn = "moe" if cfg.is_moe_layer(i) else "mlp"
        specs.append(LayerSpec(mixer, window, ffn, cross=cfg.cross_attention))
    return specs


def pattern_period(cfg: ArchConfig, role: str = "decoder") -> int:
    if role == "encoder" or cfg.family == "ssm":
        return 1
    p = 1
    if cfg.attn_every:
        p = cfg.attn_every
    if cfg.n_experts:
        p = _lcm(p, cfg.moe_every)
    if cfg.local_global_ratio:
        p = _lcm(p, cfg.local_global_ratio + 1)
    return p


def _lcm(a, b):
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ArchConfig, spec: LayerSpec, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": rmsnorm_init(cfg.d_model)}
    if spec.mixer in ("attn", "enc_attn"):
        p["mixer"] = ATT.init_attention(ks[0], cfg, dtype)
    else:
        p["mixer"] = SSM.init_mamba(ks[0], cfg, dtype)
    if spec.cross:
        p["ln_x"] = rmsnorm_init(cfg.d_model)
        p["cross"] = ATT.init_attention(ks[1], cfg, dtype)
    if spec.ffn == "mlp":
        p["ln2"] = rmsnorm_init(cfg.d_model)
        p["ffn"] = MLP.init_mlp(ks[2], cfg.d_model, cfg.d_ff, dtype)
    elif spec.ffn == "moe":
        p["ln2"] = rmsnorm_init(cfg.d_model)
        p["ffn"] = MLP.init_moe(ks[2], cfg, dtype)
    return p


def _init_stack(key, cfg: ArchConfig, role: str, dtype) -> Dict:
    specs = layer_specs(cfg, role)
    period = pattern_period(cfg, role)
    n = len(specs)
    repeats, tail_n = divmod(n, period)
    # stacked params per slot in the period (vmapped over the layer keys:
    # one program per slot, not one per layer, when init is jitted)
    slots = []
    for s in range(period):
        keys = jax.random.split(jax.random.fold_in(key, s), max(repeats, 1))
        slots.append(jax.vmap(lambda k: _init_layer(k, cfg, specs[s], dtype))(
            keys) if repeats > 0 else None)
    tail = [
        _init_layer(jax.random.fold_in(key, 10_000 + i), cfg,
                    specs[repeats * period + i], dtype)
        for i in range(tail_n)
    ]
    return {"slots": slots, "tail": tail}


def init_params(key, cfg: ArchConfig) -> Dict:
    dtype = dtype_of(cfg.dtype)
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, dtype),
        "final_ln": rmsnorm_init(cfg.d_model),
        "decoder": _init_stack(ks[1], cfg, "decoder", dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[2], cfg.vocab, cfg.d_model, dtype)
    if cfg.enc_layers:
        p["encoder"] = _init_stack(ks[3], cfg, "encoder", dtype)
        p["enc_final_ln"] = rmsnorm_init(cfg.d_model)
    if cfg.frontend_stub:
        # learned projection applied to stub frontend embeddings
        from .layers import dense_init
        p["frontend_proj"] = dense_init(ks[4], cfg.d_model, cfg.d_model, dtype)
    return p


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _stream(x, cfg: ArchConfig):
    """The residual stream entering the layers: float32 where the
    config carries it so (``residual_f32``), else as embedded."""
    return x.astype(jnp.float32) if cfg.residual_f32 else x


def _norm(x, gamma, cfg: ArchConfig):
    """RMSNorm of the residual stream; a float32 stream is normed in
    float32 and handed on in the model dtype, as the products take it."""
    h = rmsnorm(x, gamma, cfg.norm_eps)
    return h.astype(dtype_of(cfg.dtype)) if cfg.residual_f32 else h


def _apply_layer(p, spec: LayerSpec, cfg: ArchConfig, x, positions,
                 memory=None, mrope_positions=None, collect: bool = False):
    aux = jnp.zeros((), jnp.float32)
    kv = None
    h = _norm(x, p["ln1"], cfg)
    if spec.mixer == "attn":
        r = ATT.attention(p["mixer"], cfg, h, positions, window=spec.window,
                          mrope_positions=mrope_positions, return_kv=collect)
        if collect:
            h, (k, v) = r
            kv = {"k": k, "v": v}
        else:
            h = r
    elif spec.mixer == "enc_attn":
        h = ATT.attention_noncausal(p["mixer"], cfg, h, positions)
    else:
        r = SSM.mamba(p["mixer"], cfg, h, return_state=collect)
        if collect:
            h, (conv_st, ssm_st) = r
            kv = {"conv": conv_st, "ssm": ssm_st}
        else:
            h = r
    x = x + h
    if spec.cross and memory is not None:
        h = ATT.cross_attention(p["cross"], cfg,
                                _norm(x, p["ln_x"], cfg),
                                memory, positions)
        x = x + h
    if spec.ffn == "mlp":
        x = x + MLP.mlp(p["ffn"], _norm(x, p["ln2"], cfg))
    elif spec.ffn == "moe":
        h, aux = MLP.moe(p["ffn"], cfg, _norm(x, p["ln2"], cfg))
        x = x + h
    x = shard_activation(x, ("batch", "seq", None))
    return x, aux, kv


def _run_stack(stack, cfg: ArchConfig, role: str, x, positions,
               memory=None, mrope_positions=None, collect: bool = False):
    specs = layer_specs(cfg, role)
    period = pattern_period(cfg, role)
    repeats = len(specs) // period
    aux_total = jnp.zeros((), jnp.float32)
    cache = {"slots": [], "tail": []} if collect else None
    x = _stream(x, cfg)
    if repeats > 0:
        def body(carry, slot_params):
            xc, aux = carry
            kvs = []
            for s in range(period):
                p_s = gather_params_for_compute(slot_params[s])
                xc, a, kv = _apply_layer(p_s, specs[s], cfg, xc,
                                         positions, memory, mrope_positions,
                                         collect)
                aux = aux + a
                kvs.append(kv)
            return (xc, aux), (tuple(kvs) if collect else None)
        body_ck = _maybe_remat(body)
        if UNROLL:
            ys_list = []
            carry = (x, aux_total)
            for r in range(repeats):
                carry, y = body_ck(carry, jax.tree.map(lambda v: v[r],
                                                       tuple(stack["slots"])))
                ys_list.append(y)
            (x, aux_total) = carry
            ys = (jax.tree.map(lambda *vs: jnp.stack(vs), *ys_list)
                  if collect else None)
        else:
            (x, aux_total), ys = jax.lax.scan(body_ck, (x, aux_total),
                                              tuple(stack["slots"]))
        if collect:
            cache["slots"] = list(ys)
    for i, p in enumerate(stack["tail"]):
        x, a, kv = _apply_layer(p, specs[repeats * period + i], cfg, x,
                                positions, memory, mrope_positions, collect)
        aux_total = aux_total + a
        if collect:
            cache["tail"].append(kv)
    if collect:
        return x, aux_total, cache
    return x, aux_total


def _frontend_embeds(params, cfg: ArchConfig, stub: jnp.ndarray) -> jnp.ndarray:
    return stub @ params["frontend_proj"]


def _mrope_positions(cfg: ArchConfig, batch: int, seq: int):
    """(b, s, 3) positions: image patches get (0, h, w) grid, text gets
    linear (t, t, t) after the patch block (Qwen2-VL scheme)."""
    fl = cfg.frontend_len
    grid = int(math.sqrt(max(fl, 1)))
    idx = jnp.arange(seq)
    in_img = idx < fl
    h = jnp.where(in_img, (idx % max(fl, 1)) // max(grid, 1), 0)
    w = jnp.where(in_img, idx % max(grid, 1), 0)
    t = jnp.where(in_img, 0, idx - fl + grid)
    pos = jnp.stack([t, jnp.where(in_img, h, t), jnp.where(in_img, w, t)], -1)
    return jnp.broadcast_to(pos[None], (batch, seq, 3)).astype(jnp.int32)


def forward(params, cfg: ArchConfig, tokens: jnp.ndarray,
            frontend: Optional[jnp.ndarray] = None,
            enc_frontend: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits, aux_loss).

    tokens: (b, s_text). For frontend archs, ``frontend`` (b, fl, d) is
    prepended (vlm) ; for enc-dec, ``enc_frontend`` feeds the encoder.
    """
    x = embed(tokens, params["embed"])
    b = tokens.shape[0]
    mrope_pos = None
    if cfg.frontend_stub and cfg.family in ("vlm",) and frontend is not None:
        fe = _frontend_embeds(params, cfg, frontend)
        x = jnp.concatenate([fe, x], axis=1)
    seq = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(seq)[None], (b, seq))
    if cfg.mrope:
        mrope_pos = _mrope_positions(cfg, b, seq)
    x = shard_activation(x, ("batch", "seq", None))

    memory = None
    if cfg.enc_layers:
        enc_in = _frontend_embeds(params, cfg, enc_frontend)
        epos = jnp.broadcast_to(jnp.arange(enc_in.shape[1])[None],
                                (b, enc_in.shape[1]))
        memory, _ = _run_stack(params["encoder"], cfg, "encoder",
                               shard_activation(enc_in, ("batch", "seq", None)),
                               epos)
        memory = rmsnorm(memory, params["enc_final_ln"], cfg.norm_eps)

    x, aux = _run_stack(params["decoder"], cfg, "decoder", x, positions,
                        memory, mrope_pos)
    x = _norm(x, params["final_ln"], cfg)
    head = params.get("lm_head", params["embed"])
    logits = unembed(x, head)
    logits = shard_activation(logits, ("batch", "seq", "vocab"))
    return logits, aux


def prefill(params, cfg: ArchConfig, tokens: jnp.ndarray,
            frontend: Optional[jnp.ndarray] = None,
            enc_frontend: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict]:
    """Prefill: full forward that also materializes the decode cache.
    Returns (last-position logits (b, vocab), cache)."""
    x = embed(tokens, params["embed"])
    b = tokens.shape[0]
    mrope_pos = None
    if cfg.frontend_stub and cfg.family == "vlm" and frontend is not None:
        x = jnp.concatenate([_frontend_embeds(params, cfg, frontend), x], axis=1)
    seq = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(seq)[None], (b, seq))
    if cfg.mrope:
        mrope_pos = _mrope_positions(cfg, b, seq)
    x = shard_activation(x, ("batch", "seq", None))
    memory = None
    if cfg.enc_layers:
        enc_in = _frontend_embeds(params, cfg, enc_frontend)
        epos = jnp.broadcast_to(jnp.arange(enc_in.shape[1])[None],
                                (b, enc_in.shape[1]))
        memory, _ = _run_stack(params["encoder"], cfg, "encoder", enc_in, epos)
        memory = rmsnorm(memory, params["enc_final_ln"], cfg.norm_eps)
    x, _, cache = _run_stack(params["decoder"], cfg, "decoder", x, positions,
                             memory, mrope_pos, collect=True)
    x = _norm(x[:, -1:, :], params["final_ln"], cfg)
    head = params.get("lm_head", params["embed"])
    logits = unembed(x[:, 0, :], head)
    return logits, cache


# ---------------------------------------------------------------------------
# decode path (serve_step)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> Dict:
    """Cache pytree mirroring the stack structure."""
    dtype = dtype_of(cfg.dtype)
    specs = layer_specs(cfg, "decoder")
    period = pattern_period(cfg, "decoder")
    repeats = len(specs) // period
    hd = cfg.hd

    def slot_cache(spec: LayerSpec, count: int, stacked: bool):
        lead = (count,) if stacked else ()
        if spec.mixer == "attn" or spec.mixer == "enc_attn":
            shape = lead + (batch, max_len, cfg.n_kv_heads, hd)
            c = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        else:
            c = {
                "conv": jnp.zeros(lead + (batch, cfg.conv_width - 1, cfg.d_inner), dtype),
                "ssm": jnp.zeros(lead + (batch, cfg.d_inner, cfg.ssm_state), jnp.float32),
            }
        return c

    slots = [slot_cache(specs[s], repeats, True) for s in range(period)] \
        if repeats else []
    tail = [slot_cache(specs[repeats * period + i], 0, False)
            for i in range(len(specs) - repeats * period)]
    return {"slots": slots, "tail": tail}


# In a cache built by init_cache, "slots" entries are stacked over
# layer-repeats so batch is axis 1; "tail" entries are per-layer so
# batch is axis 0.  The helpers below use that structural fact (not a
# shape heuristic — matching on sizes is exactly the ``bdim is None``
# bug the serving engine used to have).

def _slot_axis_map(cache, fn_slots, fn_tail):
    return {"slots": [jax.tree.map(fn_slots, c) for c in cache["slots"]],
            "tail": [jax.tree.map(fn_tail, c) for c in cache["tail"]]}


def cache_slot_view(cache: Dict, i) -> Dict:
    """Batch-size-1 view of batch slot ``i`` (traced index ok)."""
    with jax.named_scope("kv_cache"):
        return _slot_axis_map(
            cache,
            lambda v: jax.lax.dynamic_slice_in_dim(v, i, 1, axis=1),
            lambda v: jax.lax.dynamic_slice_in_dim(v, i, 1, axis=0))


def cache_slot_write(cache: Dict, sub: Dict, i) -> Dict:
    """Write a b=1 sub-cache (from :func:`cache_slot_view`) back at slot
    ``i``; under jit with donated operands this is an in-place row
    update, not a full-cache copy."""
    def wr(axis):
        return lambda v, s: jax.lax.dynamic_update_slice_in_dim(
            v, s.astype(v.dtype), i, axis=axis)
    with jax.named_scope("kv_cache"):
        return {"slots": [jax.tree.map(wr(1), c, sc)
                          for c, sc in zip(cache["slots"], sub["slots"])],
                "tail": [jax.tree.map(wr(0), c, sc)
                         for c, sc in zip(cache["tail"], sub["tail"])]}


def zero_cache_slot(cache: Dict, i) -> Dict:
    """Zero every cache row of batch slot ``i`` — reused-slot hygiene:
    a new request admitted into a slot must never see KV rows, conv
    tails or SSM state left by a longer previous occupant."""
    def z(axis):
        def go(v):
            row = jax.lax.dynamic_slice_in_dim(v, i, 1, axis=axis)
            return jax.lax.dynamic_update_slice_in_dim(
                v, jnp.zeros_like(row), i, axis=axis)
        return go
    with jax.named_scope("kv_cache"):
        return _slot_axis_map(cache, z(1), z(0))


def _decode_layer(p, spec: LayerSpec, cfg: ArchConfig, x, cache, cache_len,
                  memory=None, mrope_positions=None):
    h = _norm(x, p["ln1"], cfg)
    if spec.mixer == "attn":
        h, k_all, v_all = ATT.decode_attention(
            p["mixer"], cfg, h, cache["k"], cache["v"], cache_len,
            window=spec.window, mrope_positions=mrope_positions)
        new_cache = {"k": k_all, "v": v_all}
    else:
        h, conv, ssm_st = SSM.mamba_decode(p["mixer"], cfg, h,
                                           cache["conv"], cache["ssm"])
        new_cache = {"conv": conv, "ssm": ssm_st}
    x = x + h
    if spec.cross and memory is not None:
        b = x.shape[0]
        pos = jnp.full((b, 1), cache_len, jnp.int32)
        x = x + ATT.cross_attention(p["cross"], cfg,
                                    _norm(x, p["ln_x"], cfg),
                                    memory, pos)
    if spec.ffn == "mlp":
        x = x + MLP.mlp(p["ffn"], _norm(x, p["ln2"], cfg))
    elif spec.ffn == "moe":
        h, _ = MLP.moe(p["ffn"], cfg, _norm(x, p["ln2"], cfg))
        x = x + h
    return x, new_cache


def decode_step(params, cfg: ArchConfig, token: jnp.ndarray, cache: Dict,
                cache_len: jnp.ndarray, memory: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. token: (b, 1) int32; returns (logits (b, vocab),
    new cache)."""
    specs = layer_specs(cfg, "decoder")
    period = pattern_period(cfg, "decoder")
    repeats = len(specs) // period
    x = _stream(embed(token, params["embed"]), cfg)
    mrope_pos = None
    if cfg.mrope:
        b = token.shape[0]
        base = _mrope_positions(cfg, b, 1)
        mrope_pos = base + cache_len.astype(jnp.int32)
    new_cache: Dict[str, Any] = {"slots": [], "tail": []}
    if repeats:
        def body(carry, xs):
            xc = carry
            slot_params, slot_caches = xs
            new_slots = []
            for s in range(period):
                p_s = gather_params_for_compute(slot_params[s])
                xc, nc = _decode_layer(p_s, specs[s], cfg, xc,
                                       slot_caches[s], cache_len, memory,
                                       mrope_pos)
                new_slots.append(nc)
            return xc, tuple(new_slots)
        scan_xs = (tuple(params["decoder"]["slots"]), tuple(cache["slots"]))
        if UNROLL:
            ys_list = []
            for r in range(repeats):
                x, y = body(x, jax.tree.map(lambda v: v[r], scan_xs))
                ys_list.append(y)
            new_slots = jax.tree.map(lambda *vs: jnp.stack(vs), *ys_list)
        else:
            x, new_slots = jax.lax.scan(body, x, scan_xs)
        new_cache["slots"] = list(new_slots)
    for i, p in enumerate(params["decoder"]["tail"]):
        x, nc = _decode_layer(p, specs[repeats * period + i], cfg, x,
                              cache["tail"][i], cache_len, memory, mrope_pos)
        new_cache["tail"].append(nc)
    x = _norm(x, params["final_ln"], cfg)
    head = params.get("lm_head", params["embed"])
    logits = unembed(x[:, 0, :], head)
    return logits, new_cache


# ---------------------------------------------------------------------------
# serving fast path: chunked prefill + ragged paged decode
# ---------------------------------------------------------------------------

def _chunk_layer(p, spec: LayerSpec, cfg: ArchConfig, x, cache, offset):
    h = _norm(x, p["ln1"], cfg)
    if spec.mixer == "attn":
        h, k_rows, v_rows = ATT.chunk_attention(
            p["mixer"], cfg, h, cache["k"], cache["v"], offset,
            window=spec.window)
        out = {"k": k_rows, "v": v_rows}
    else:
        h, conv, ssm_st = SSM.mamba_chunk(p["mixer"], cfg, h,
                                          cache["conv"], cache["ssm"])
        out = {"conv": conv, "ssm": ssm_st}
    x = x + h
    if spec.ffn == "mlp":
        x = x + MLP.mlp(p["ffn"], _norm(x, p["ln2"], cfg))
    elif spec.ffn == "moe":
        h, _ = MLP.moe(p["ffn"], cfg, _norm(x, p["ln2"], cfg))
        x = x + h
    return x, out


def _kv_prefix(v, layer, kv_len: int):
    """Rows ``[:kv_len]`` of one layer's K or V: of layer ``layer`` (a
    traced index) of a stacked (repeats, b, S, hkv, hd) cache, or of a
    tail layer's (b, S, hkv, hd) cache when ``layer`` is None."""
    if layer is None:
        return v[:, :kv_len]
    return jax.lax.dynamic_slice(
        v, (layer, 0, 0, 0, 0), (1, v.shape[1], kv_len) + v.shape[3:])[0]


def _put_rows(v, rows, starts):
    """Write ``rows`` (..., b, n, hkv, hd) into the cache ``v`` (..., b,
    S, hkv, hd), slot ``i``'s at row ``starts[i]`` (a scalar ``starts``
    serves every slot): one ``dynamic_update_slice`` of the rows alone
    per slot, in place on a donated cache."""
    rows = rows.astype(v.dtype)
    lead = (0,) * (v.ndim - 4)
    b = v.shape[-4]
    starts = jnp.broadcast_to(starts, (b,))
    for i in range(b):
        v = jax.lax.dynamic_update_slice(
            v, rows[..., i:i + 1, :, :, :], lead + (i, starts[i], 0, 0))
    return v


def _stack_walk(params, cfg: ArchConfig, x, cache, layer_fn, kv_len: int,
                starts):
    """Shared slot-scan + tail walk for the serving step functions:
    ``layer_fn(p, spec, x, layer_cache) -> (x, out)``.

    An attention layer is handed its K/V prefix ``[:, :kv_len]`` and
    returns only the K/V rows it produced; the walk writes them once, in
    place, at row ``starts`` (:func:`_put_rows`) — after the scan for
    the stacked layers, after the layer for a tail layer.  The stacked
    K/V are a loop-invariant operand of the scan, each layer reading its
    prefix by dynamic index, so no whole-layer cache passes through the
    scan or is restacked.  An SSM layer rewrites its whole state on
    every step: it is handed that state and returns it whole, through
    the scan's ``xs``/``ys``.

    Named scopes: each layer's own operations are ``layer`` (with the
    layer functions' ``attention``/``ssm``/``mlp``/``moe`` inside); the
    walk's own — the scan that slices each layer's weights, the prefix
    reads, the SSM states' restacking and the row writes — are
    ``kv_cache``."""
    specs = layer_specs(cfg, "decoder")
    period = pattern_period(cfg, "decoder")
    repeats = len(specs) // period
    new_cache: Dict[str, Any] = {"slots": [], "tail": []}
    x = _stream(x, cfg)

    def layer(p, spec, xc, lc):
        with jax.named_scope("layer"):
            return layer_fn(p, spec, xc, lc)

    def prefix(c, r):
        with jax.named_scope("kv_cache"):
            return jax.tree.map(lambda v: _kv_prefix(v, r, kv_len), c)

    def put(c, rows):
        with jax.named_scope("kv_cache"):
            return jax.tree.map(lambda v, n: _put_rows(v, n, starts), c, rows)

    if repeats:
        kv = [specs[s].mixer == "attn" for s in range(period)]
        slots = cache["slots"]

        def body(carry, xs):
            xc = carry
            r, slot_params, states = xs
            outs = []
            for s in range(period):
                p_s = gather_params_for_compute(slot_params[s])
                lc = prefix(slots[s], r) if kv[s] else states[s]
                xc, out = layer(p_s, specs[s], xc, lc)
                outs.append(out)
            return xc, tuple(outs)
        states = tuple(None if kv[s] else slots[s] for s in range(period))
        scan_xs = (jnp.arange(repeats), tuple(params["decoder"]["slots"]),
                   states)
        with jax.named_scope("kv_cache"):
            if UNROLL:
                ys_list = []
                for r in range(repeats):
                    x, y = body(x, jax.tree.map(lambda v: v[r], scan_xs))
                    ys_list.append(y)
                outs = jax.tree.map(lambda *vs: jnp.stack(vs), *ys_list)
            else:
                x, outs = jax.lax.scan(body, x, scan_xs)
        new_cache["slots"] = [put(slots[s], outs[s]) if kv[s] else outs[s]
                              for s in range(period)]
    for i, p in enumerate(params["decoder"]["tail"]):
        spec = specs[repeats * period + i]
        c = cache["tail"][i]
        if spec.mixer == "attn":
            x, rows = layer(p, spec, x, prefix(c, None))
            c = put(c, rows)
        else:
            x, c = layer(p, spec, x, c)
        new_cache["tail"].append(c)
    return x, new_cache


def chunk_step(params, cfg: ArchConfig, tokens: jnp.ndarray, cache: Dict,
               offset, kv_len: int) -> Tuple[jnp.ndarray, Dict]:
    """Prefill one chunk of a sequence into an existing cache.

    tokens: (b, c) — rows ``[offset, offset+c)`` of the prompt (offset a
    traced scalar, 0 for the first chunk); cache: (typically a b=1
    :func:`cache_slot_view`) with all rows < offset already prefilled;
    kv_len: static page-aligned prefix covering ``offset + c``.
    Returns (logits (b, c, vocab) for *every* chunk position — the
    caller picks the last real one to seed decoding — and the cache with
    the chunk's K/V rows and the SSM states updated)."""
    with jax.named_scope("embed"):
        x = embed(tokens, params["embed"])
    x = shard_activation(x, ("batch", "seq", None))
    x, new_cache = _stack_walk(
        params, cfg, x, cache,
        lambda p, spec, xc, lc: _chunk_layer(p, spec, cfg, xc, lc, offset),
        kv_len, offset)
    with jax.named_scope("head"):
        x = _norm(x, params["final_ln"], cfg)
        head = params.get("lm_head", params["embed"])
        logits = unembed(x, head)
    return logits, new_cache


def _serve_decode_layer(p, spec: LayerSpec, cfg: ArchConfig, x, cache,
                        lengths, active):
    h = _norm(x, p["ln1"], cfg)
    if spec.mixer == "attn":
        h, k_row, v_row = ATT.paged_decode_attention(
            p["mixer"], cfg, h, cache["k"], cache["v"], lengths,
            window=spec.window)
        # inactive slots (mid-prefill / retired) write at their own
        # lengths[i] — a row the next prefill chunk or admission zeroing
        # overwrites, so no select is needed on the KV pages
        out = {"k": k_row, "v": v_row}
    else:
        h, conv, ssm_st = SSM.mamba_decode(p["mixer"], cfg, h,
                                           cache["conv"], cache["ssm"])
        # the recurrent states are the *carry* of an in-flight prefill:
        # a garbage decode update would corrupt the next chunk, so keep
        # inactive slots' states untouched
        sel = active[:, None, None]
        out = {"conv": jnp.where(sel, conv, cache["conv"]),
               "ssm": jnp.where(sel, ssm_st, cache["ssm"])}
    x = x + h
    if spec.ffn == "mlp":
        x = x + MLP.mlp(p["ffn"], _norm(x, p["ln2"], cfg))
    elif spec.ffn == "moe":
        h, _ = MLP.moe(p["ffn"], cfg, _norm(x, p["ln2"], cfg))
        x = x + h
    return x, out


def serve_decode_step(params, cfg: ArchConfig, token: jnp.ndarray,
                      cache: Dict, lengths: jnp.ndarray,
                      active: jnp.ndarray, kv_len: int
                      ) -> Tuple[jnp.ndarray, Dict]:
    """Ragged continuous-batching decode step.

    token: (b, 1) int32; lengths: (b,) per-slot valid cache lengths
    (each slot attends to and extends its *own* prefix — no shared
    ``max(lengths)``); active: (b,) bool — slots currently decoding;
    kv_len: static page-aligned bound ≥ max(lengths)+1.  Returns
    (logits (b, vocab), the cache with one K/V row per slot written at
    ``lengths`` and the active slots' SSM states updated)."""
    with jax.named_scope("embed"):
        x = embed(token, params["embed"])
    x, new_cache = _stack_walk(
        params, cfg, x, cache,
        lambda p, spec, xc, lc: _serve_decode_layer(p, spec, cfg, xc, lc,
                                                    lengths, active),
        kv_len, lengths)
    with jax.named_scope("head"):
        x = _norm(x, params["final_ln"], cfg)
        head = params.get("lm_head", params["embed"])
        logits = unembed(x[:, 0, :], head)
    return logits, new_cache


# ---------------------------------------------------------------------------
# losses / steps
# ---------------------------------------------------------------------------

def lm_loss(params, cfg: ArchConfig, tokens, labels, frontend=None,
            enc_frontend=None) -> jnp.ndarray:
    logits, aux = forward(params, cfg, tokens, frontend, enc_frontend)
    # frontend positions don't produce next-token predictions
    if logits.shape[1] != labels.shape[1]:
        logits = logits[:, -labels.shape[1]:, :]
    lf = logits.astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + 0.01 * aux
