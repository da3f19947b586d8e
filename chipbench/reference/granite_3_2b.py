"""Plain float32 reference of the Granite-3.0 decoder (dense GQA), and
the serving parameters it is compared against, both made from the seed.

It follows the published Granite equations with the multipliers and
constants read from the configuration file as it is run
(``configs/granite_3_2b.json``): embedding, attention, residual and logit
multipliers, RoPE base, RMSNorm epsilon, tied or separate output head.
Nothing here imports the serving program; ``make_params`` writes the
weights into the program's parameter layout, and ``Reference`` makes the
same weights again, one layer at a time, from the seed alone.

    x0 = E[tokens] * embedding_multiplier
    h  = RMSNorm(x) ; q, k, v = h Wq, h Wk, h Wv ; RoPE(q, k)
    x += (softmax(q k^T * attention_multiplier, causal) v) Wo * residual_multiplier
    x += (silu(RMSNorm(x) Wg) * (RMSNorm(x) Wu)) Wd * residual_multiplier
    logits = RMSNorm(x) H^T / logits_scaling

RMSNorm weights are stored as offsets from one (``1 + gamma``), the
serving program's layout; the product is the same.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from harness import refkit as K
from harness import weights as W

EMBED, HEAD, FINAL_LN = 1, 2, 3
LN1, WQ, WK, WV, WO, LN2, WG, WU, WD = range(10, 19)
NORM_STD = 0.05
EMBED_STD = 0.02


class Dims(NamedTuple):
    d: int
    heads: int
    kv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    emb_mult: float
    attn_mult: float
    res_mult: float
    logit_scale: float
    tied: bool


def dims(conf: dict) -> Dims:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return Dims(d, h, conf["num_key_value_heads"], d // h,
                conf["intermediate_size"], conf["vocab_size"],
                conf["num_hidden_layers"], conf["rms_norm_eps"],
                conf["rope_theta"], conf["embedding_multiplier"],
                conf["attention_multiplier"], conf["residual_multiplier"],
                conf["logits_scaling"], conf["tie_word_embeddings"])


def program_sizes(conf: dict) -> dict:
    """The serving program's own fields for the sizes this file states
    (``ArchConfig.scaled``), so the program runs the configuration as
    the file gives it."""
    return {"n_layers": conf["num_hidden_layers"],
            "d_model": conf["hidden_size"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "d_ff": conf["intermediate_size"], "vocab": conf["vocab_size"]}


def _dense(words, leaf, layer, n_in, n_out):
    return W.uniform(words, leaf, layer, (n_in, n_out),
                     1.0 / math.sqrt(n_in)).astype(jnp.bfloat16)


def _norm(words, leaf, layer, n):
    return W.uniform(words, leaf, layer, (n,), NORM_STD)


def layer_tree(m: Dims, words, layer) -> dict:
    """One layer's weights in the serving layout and dtypes."""
    d, hd = m.d, m.hd
    return {
        "ln1": _norm(words, LN1, layer, d),
        "mixer": {"wq": _dense(words, WQ, layer, d, m.heads * hd),
                  "wk": _dense(words, WK, layer, d, m.kv * hd),
                  "wv": _dense(words, WV, layer, d, m.kv * hd),
                  "wo": _dense(words, WO, layer, m.heads * hd, d)},
        "ln2": _norm(words, LN2, layer, d),
        "ffn": {"w_gate": _dense(words, WG, layer, d, m.ff),
                "w_up": _dense(words, WU, layer, d, m.ff),
                "w_down": _dense(words, WD, layer, m.ff, d)},
    }


def _table(words, leaf, m: Dims):
    return W.uniform(words, leaf, 0, (m.vocab, m.d),
                     EMBED_STD).astype(jnp.bfloat16)


def make_params(conf: dict, seed: int):
    """The serving parameters, made on the device by one jitted call."""
    return _build(dims(conf), W.seed_words(seed))


@functools.partial(jax.jit, static_argnums=0)
def _build(m: Dims, words):
    p = {"embed": _table(words, EMBED, m),
         "final_ln": _norm(words, FINAL_LN, 0, m.d),
         "decoder": {"slots": [W.stacked(
             lambda i: layer_tree(m, words, i), m.layers)], "tail": []}}
    if not m.tied:
        p["lm_head"] = _table(words, HEAD, m)
    return p


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(m: Dims, precision: str, x, w):
    b, s, _ = x.shape
    pos = jnp.arange(s)
    h = K.rmsnorm(x, 1.0 + w["ln1"], m.eps)
    a = w["mixer"]
    q = K.matmul(h, a["wq"], precision).reshape(b, s, m.heads, m.hd)
    k = K.matmul(h, a["wk"], precision).reshape(b, s, m.kv, m.hd)
    v = K.matmul(h, a["wv"], precision).reshape(b, s, m.kv, m.hd)
    q = jax.vmap(lambda t: K.rope(t, pos, m.theta))(q)
    k = jax.vmap(lambda t: K.rope(t, pos, m.theta))(k)
    att = jax.lax.map(lambda qkv: K.causal_attention(
        *qkv, m.attn_mult, precision), (q, k, v))
    x = x + K.matmul(att.reshape(b, s, -1), a["wo"], precision) * m.res_mult
    h = K.rmsnorm(x, 1.0 + w["ln2"], m.eps)
    f = w["ffn"]
    g = K.silu(K.matmul(h, f["w_gate"], precision)) \
        * K.matmul(h, f["w_up"], precision)
    return x + K.matmul(g, f["w_down"], precision) * m.res_mult


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(m: Dims, words, layer):
    return layer_tree(m, words, layer)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(m: Dims, leaf: int, words):
    return _table(words, leaf, m)


@functools.partial(jax.jit, static_argnums=0)
def _embed_rows(m: Dims, words, tokens):
    return jnp.take(_table(words, EMBED, m), tokens, axis=0).astype(
        jnp.float32) * m.emb_mult


@functools.partial(jax.jit, static_argnums=0)
def _final(m: Dims, words, x):
    return K.rmsnorm(x, 1.0 + _norm(words, FINAL_LN, 0, m.d), m.eps)


class Reference:
    """The reference for one seed: ``hidden`` runs whole sequences
    layer by layer; ``logits`` applies the output head to rows of it."""

    def __init__(self, conf: dict, seed: int):
        self.m = dims(conf)
        self.words = W.seed_words(seed)
        self._head = None

    def hidden(self, tokens, precision: str) -> jnp.ndarray:
        """tokens (B, S) int -> final-normed hidden states (B, S, d)."""
        m = self.m
        x = _embed_rows(m, self.words, jnp.asarray(tokens, jnp.int32))
        for i in range(m.layers):
            x = _layer(m, precision, x,
                       _layer_weights(m, self.words, jnp.uint32(i)))
        return _final(m, self.words, x)

    def logits(self, rows: jnp.ndarray, precision: str) -> jnp.ndarray:
        """rows (P, d) of ``hidden`` -> logits (P, vocab), float32."""
        m = self.m
        if self._head is None:
            self._head = _embed(m, EMBED if m.tied else HEAD, self.words)
        return _head_logits(precision, m.logit_scale, rows, self._head)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head_logits(precision: str, scale: float, rows, head):
    return K.matmul(rows, head.T, precision) / scale


def shapes(conf: dict) -> dict:
    """What the work counts need: per-layer blocks and the parameters
    a token passes through (the output head counts, the embedding
    lookup does not)."""
    m = dims(conf)
    attn = m.d * m.heads * m.hd * 2 + 2 * m.d * m.kv * m.hd
    return {"layers": m.layers,
            "attention": {"heads": m.heads, "kv_heads": m.kv,
                          "head_dim": m.hd},
            "mlp": {"d": m.d, "ff": m.ff},
            "active_params": m.layers * (attn + 3 * m.d * m.ff)
            + m.vocab * m.d}
