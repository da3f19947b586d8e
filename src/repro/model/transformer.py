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
# one layer, one walk, one head
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


def _block(p, spec: LayerSpec, cfg: ArchConfig, x, mix, memory=None,
           cross_pos=None):
    """One layer: norm → mixer → residual → cross-attention (where the
    layer has it and ``memory`` is given, at ``cross_pos``) → MLP or MoE
    FFN, in the ``layer`` named scope.

    ``mix(p_mixer, h) -> (h, out)`` is the calling path's own mixer:
    the attention or SSM call, and what it hands back (``out``: K/V or
    states).  Returns (x, aux, out); ``aux`` is the MoE balance loss."""
    with jax.named_scope("layer"):
        aux = jnp.zeros((), jnp.float32)
        h, out = mix(p["mixer"], _norm(x, p["ln1"], cfg))
        x = x + h
        if spec.cross and memory is not None:
            x = x + ATT.cross_attention(p["cross"], cfg,
                                        _norm(x, p["ln_x"], cfg),
                                        memory, cross_pos)
        if spec.ffn == "mlp":
            x = x + MLP.mlp(p["ffn"], _norm(x, p["ln2"], cfg))
        elif spec.ffn == "moe":
            h, aux = MLP.moe(p["ffn"], cfg, _norm(x, p["ln2"], cfg))
            x = x + h
        return x, aux, out


def _walk(stack, cfg: ArchConfig, role: str, x, layer, xs=None, wrap=None,
          scanned=None):
    """Run ``layer`` over a stack: the pattern period over the stacked
    repeats by ``lax.scan`` (a Python loop under ``UNROLL``), then the
    tail layers, on the residual stream as :func:`_stream` makes it.

    ``layer(p, spec, x, lx, at) -> (x, y)``.  ``p`` is the layer's
    params, a stacked layer's slice re-constrained by
    ``gather_params_for_compute``; ``lx`` its entry of ``xs``
    (``{"slots": [...], "tail": [...]}`` in the stack's layout, the
    stacked entries scanned beside their params; None without ``xs``);
    ``at`` is ``("slots", s, r)``, ``r`` the repeat index (traced in the
    scan), or ``("tail", i, None)``, for what a layer reads by index.
    Returns (x, the ``y``s in the same layout, stacked over repeats).
    ``wrap`` wraps the scan body (activation checkpointing);
    ``scanned(ys)`` maps the stacked layers' ``y``s as soon as the scan
    ends, before the tail layers run.  The scan and what it does for
    itself — slicing each layer's weights, stacking the ``y``s — is the
    ``kv_cache`` named scope."""
    specs = layer_specs(cfg, role)
    period = pattern_period(cfg, role)
    repeats = len(specs) // period
    xs = xs or {"slots": [None] * period, "tail": [None] * len(stack["tail"])}
    ys: Dict[str, List] = {"slots": [], "tail": []}
    x = _stream(x, cfg)
    if repeats:
        def body(xc, xs_r):
            r, slot_params, lxs = xs_r
            outs = []
            for s in range(period):
                xc, y = layer(gather_params_for_compute(slot_params[s]),
                              specs[s], xc, lxs[s], ("slots", s, r))
                outs.append(y)
            return xc, tuple(outs)
        body = wrap(body) if wrap else body
        scan_xs = (jnp.arange(repeats), tuple(stack["slots"]),
                   tuple(xs["slots"]))
        with jax.named_scope("kv_cache"):
            if UNROLL:
                ys_list = []
                for r in range(repeats):
                    x, y = body(x, jax.tree.map(lambda v: v[r], scan_xs))
                    ys_list.append(y)
                outs = jax.tree.map(lambda *vs: jnp.stack(vs), *ys_list)
            else:
                x, outs = jax.lax.scan(body, x, scan_xs)
        ys["slots"] = scanned(list(outs)) if scanned else list(outs)
    for i, p in enumerate(stack["tail"]):
        x, y = layer(p, specs[repeats * period + i], x, xs["tail"][i],
                     ("tail", i, None))
        ys["tail"].append(y)
    return x, ys


def _head(params, cfg: ArchConfig, x, pos=None):
    """The final norm, then the logits: of every position, or of
    position ``pos`` alone, (b, vocab)."""
    with jax.named_scope("head"):
        x = _norm(x, params["final_ln"], cfg)
        if pos is not None:
            x = x[:, pos, :]
        return unembed(x, params.get("lm_head", params["embed"]))


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _run_stack(stack, cfg: ArchConfig, role: str, x, positions,
               memory=None, mrope_positions=None, collect: bool = False):
    """The whole-sequence walk: returns (x, aux) and, with ``collect``,
    the decode cache the layers' K/V and final states make."""
    def layer(p, spec, x, _, at):
        def mix(pm, h):
            if spec.mixer == "enc_attn":
                return ATT.attention_noncausal(pm, cfg, h, positions), None
            if spec.mixer == "attn":
                res = ATT.attention(pm, cfg, h, positions, window=spec.window,
                                    mrope_positions=mrope_positions,
                                    return_kv=collect)
                names = ("k", "v")
            else:
                res = SSM.mamba(pm, cfg, h, return_state=collect)
                names = ("conv", "ssm")
            if not collect:
                return res, None
            h, state = res
            return h, dict(zip(names, state))
        x, aux, out = _block(p, spec, cfg, x, mix, memory, positions)
        return shard_activation(x, ("batch", "seq", None)), (aux, out)

    x, ys = _walk(stack, cfg, role, x, layer, wrap=_maybe_remat)
    aux = sum(jnp.sum(a) for a, _ in ys["slots"] + ys["tail"])
    if collect:
        return x, aux, {g: [c for _, c in ys[g]] for g in ("slots", "tail")}
    return x, aux


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


def _inputs(params, cfg: ArchConfig, tokens, frontend, enc_frontend):
    """What the decoder stack takes in ``forward`` and ``prefill``: the
    embedded tokens (behind the frontend's embeddings for a VLM), their
    positions and M-RoPE positions, and the encoder's memory."""
    x = embed(tokens, params["embed"])
    b = tokens.shape[0]
    if cfg.frontend_stub and cfg.family == "vlm" and frontend is not None:
        x = jnp.concatenate([_frontend_embeds(params, cfg, frontend), x],
                            axis=1)
    seq = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(seq)[None], (b, seq))
    mrope_pos = _mrope_positions(cfg, b, seq) if cfg.mrope else None
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
    return x, positions, mrope_pos, memory


def forward(params, cfg: ArchConfig, tokens: jnp.ndarray,
            frontend: Optional[jnp.ndarray] = None,
            enc_frontend: Optional[jnp.ndarray] = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward. Returns (logits, aux_loss).

    tokens: (b, s_text). For frontend archs, ``frontend`` (b, fl, d) is
    prepended (vlm) ; for enc-dec, ``enc_frontend`` feeds the encoder.
    """
    x, positions, mrope_pos, memory = _inputs(params, cfg, tokens, frontend,
                                              enc_frontend)
    x, aux = _run_stack(params["decoder"], cfg, "decoder", x, positions,
                        memory, mrope_pos)
    logits = shard_activation(_head(params, cfg, x),
                              ("batch", "seq", "vocab"))
    return logits, aux


def prefill(params, cfg: ArchConfig, tokens: jnp.ndarray,
            frontend: Optional[jnp.ndarray] = None,
            enc_frontend: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict]:
    """Prefill: full forward that also materializes the decode cache.
    Returns (last-position logits (b, vocab), cache)."""
    x, positions, mrope_pos, memory = _inputs(params, cfg, tokens, frontend,
                                              enc_frontend)
    x, _, cache = _run_stack(params["decoder"], cfg, "decoder", x, positions,
                             memory, mrope_pos, collect=True)
    return _head(params, cfg, x[:, -1:, :], 0), cache


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
# batch is axis 0.  The helpers below use that structural fact, not a
# shape heuristic.

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


def decode_step(params, cfg: ArchConfig, token: jnp.ndarray, cache: Dict,
                cache_len: jnp.ndarray, memory: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step over whole caches, every slot at ``cache_len``:
    the reference the serving steps are checked against.
    token: (b, 1) int32; returns (logits (b, vocab), new cache)."""
    b = token.shape[0]
    mrope_pos = None
    if cfg.mrope:
        mrope_pos = _mrope_positions(cfg, b, 1) + cache_len.astype(jnp.int32)
    cross_pos = (jnp.full((b, 1), cache_len, jnp.int32)
                 if memory is not None else None)

    def layer(p, spec, x, c, at):
        def mix(pm, h):
            if spec.mixer == "attn":
                h, k, v = ATT.decode_attention(
                    pm, cfg, h, c["k"], c["v"], cache_len,
                    window=spec.window, mrope_positions=mrope_pos)
                return h, {"k": k, "v": v}
            h, conv, ssm_st = SSM.mamba_decode(pm, cfg, h, c["conv"],
                                               c["ssm"])
            return h, {"conv": conv, "ssm": ssm_st}
        x, _, out = _block(p, spec, cfg, x, mix, memory, cross_pos)
        return x, out

    x, new_cache = _walk(params["decoder"], cfg, "decoder",
                         embed(token, params["embed"]), layer, cache)
    return _head(params, cfg, x, 0), new_cache


# ---------------------------------------------------------------------------
# serving fast path: chunked prefill + ragged paged decode
# ---------------------------------------------------------------------------

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


def _stack_walk(params, cfg: ArchConfig, x, cache, mix, kv_len: int, starts):
    """The serving steps' walk: :func:`_walk` with ``mix(spec, p_mixer,
    h, layer_cache) -> (h, out)`` as each layer's mixer.

    An attention layer is handed its K/V prefix ``[:, :kv_len]`` and
    returns only the K/V rows it produced; they are written once, in
    place, at row ``starts`` (:func:`_put_rows`) — after the scan for
    the stacked layers, after the layer for a tail layer.  The stacked
    K/V are a loop-invariant operand of the scan, each layer reading its
    prefix by dynamic index, so no whole-layer cache passes through the
    scan or is restacked.  An SSM layer rewrites its whole state on
    every step: it is handed that state and returns it whole, through
    the scan's ``xs``/``ys``.

    Named scopes: each layer's own operations are ``layer`` (with the
    mixers' ``attention``/``ssm`` and the FFN's ``mlp``/``moe`` inside);
    the walk's own — the scan that slices each layer's weights, the
    prefix reads, the SSM states' restacking and the row writes — are
    ``kv_cache``."""
    def prefix(c, r):
        with jax.named_scope("kv_cache"):
            return jax.tree.map(lambda v: _kv_prefix(v, r, kv_len), c)

    def put(c, rows):
        with jax.named_scope("kv_cache"):
            return jax.tree.map(lambda v, n: _put_rows(v, n, starts), c, rows)

    def layer(p, spec, xc, lc, at):
        group, j, r = at
        attn = spec.mixer == "attn"
        if attn:
            lc = prefix(cache[group][j], r)
        xc, _, out = _block(p, spec, cfg, xc,
                            lambda pm, h: mix(spec, pm, h, lc))
        if attn and r is None:
            out = put(cache[group][j], out)
        return xc, out

    def states(group):
        # what goes through the scan: an SSM layer's states, no K/V
        return [None if "k" in c else c for c in cache[group]]

    def put_scanned(ys):
        return [put(c, y) if "k" in c else y
                for c, y in zip(cache["slots"], ys)]

    return _walk(params["decoder"], cfg, "decoder", x, layer,
                 {"slots": states("slots"), "tail": states("tail")},
                 scanned=put_scanned)


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
    def mix(spec, pm, h, c):
        if spec.mixer == "attn":
            h, k_rows, v_rows = ATT.chunk_attention(
                pm, cfg, h, c["k"], c["v"], offset, window=spec.window)
            return h, {"k": k_rows, "v": v_rows}
        h, conv, ssm_st = SSM.mamba_chunk(pm, cfg, h, c["conv"], c["ssm"])
        return h, {"conv": conv, "ssm": ssm_st}

    with jax.named_scope("embed"):
        x = embed(tokens, params["embed"])
    x = shard_activation(x, ("batch", "seq", None))
    x, new_cache = _stack_walk(params, cfg, x, cache, mix, kv_len, offset)
    return _head(params, cfg, x), new_cache


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
    def mix(spec, pm, h, c):
        if spec.mixer == "attn":
            h, k_row, v_row = ATT.paged_decode_attention(
                pm, cfg, h, c["k"], c["v"], lengths, window=spec.window)
            # inactive slots (mid-prefill / retired) write at their own
            # lengths[i] — a row the next prefill chunk or admission
            # zeroing overwrites, so no select is needed on the KV pages
            return h, {"k": k_row, "v": v_row}
        h, conv, ssm_st = SSM.mamba_decode(pm, cfg, h, c["conv"], c["ssm"])
        # the recurrent states are the *carry* of an in-flight prefill:
        # a garbage decode update would corrupt the next chunk, so keep
        # inactive slots' states untouched
        sel = active[:, None, None]
        return h, {"conv": jnp.where(sel, conv, c["conv"]),
                   "ssm": jnp.where(sel, ssm_st, c["ssm"])}

    with jax.named_scope("embed"):
        x = embed(token, params["embed"])
    x, new_cache = _stack_walk(params, cfg, x, cache, mix, kv_len, lengths)
    return _head(params, cfg, x, 0), new_cache


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
