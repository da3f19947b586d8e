"""Plain float32 reference of the Falcon-Mamba decoder (pure Mamba-1),
and the serving parameters it is compared against, both made from the
seed.

It follows the published block (hf:tiiuae/falcon-mamba-7b,
arXiv:2410.05355) with the sizes and epsilons read from the
configuration file as it is run (``configs/falcon_mamba_7b.json``).
Nothing here imports the serving program; ``make_params`` writes the
weights into the program's parameter layout, and ``Reference`` makes the
same weights again, one layer at a time, from the seed alone.

    h        = RMSNorm(x)                         (eps: layer_norm_epsilon)
    xs, z    = split(h W_in)                      (d_inner each)
    xs       = silu(causal depthwise conv(xs) + conv_bias)
    r, B, C  = split(xs W_x)                      (time_step_rank, state, state)
    r, B, C  = rms(r), rms(B), rms(C)             (weight-free, eps: mixer_rms_eps)
    dt       = softplus(r W_dt + dt_bias)         (d_inner)
    h_t      = exp(dt_t A) * h_{t-1} + dt_t B_t x_t,   A = -exp(A_log)
    y_t      = h_t . C_t + D * x_t
    x       += (y * silu(z)) W_out
    logits   = RMSNorm(x) H^T

The recurrence runs step by step over the sequence, carrying ``h``
(batch, d_inner, state), so no (sequence, d_inner, state) tensor is ever
held.  RMSNorm weights are stored as offsets from one (``1 + gamma``),
the serving program's layout; the product is the same.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import refkit as K
from harness import weights as W

EMBED, HEAD, FINAL_LN = 1, 2, 3
LN1, W_IN, CONV_W, CONV_B, W_X, W_DT, DT_BIAS, D_SKIP, W_OUT = range(10, 19)
NORM_STD = 0.05
EMBED_STD = 0.02
CONV_B_STD = 0.1
#: dt_bias is drawn from the inverse softplus of 16 time steps spaced
#: evenly in log between Mamba's dt_min 0.001 and dt_max 0.1; D from 16
#: values spread round Mamba's initial 1.  Integer hashes pick the entry,
#: so the values are exact on every backend however they are batched.
_DT = np.exp(np.log(1e-3) + (np.arange(16) + 0.5) / 16
             * (np.log(1e-1) - np.log(1e-3)))
DT_BIAS_TABLE = (_DT + np.log(-np.expm1(-_DT))).astype(np.float32)
D_TABLE = np.linspace(0.5, 1.5, 16, dtype=np.float32)


class Dims(NamedTuple):
    d: int
    di: int
    st: int
    rank: int
    cw: int
    vocab: int
    layers: int
    eps: float
    mixer_eps: float
    tied: bool


def dims(conf: dict) -> Dims:
    return Dims(conf["hidden_size"], conf["intermediate_size"],
                conf["state_size"], conf["time_step_rank"],
                conf["conv_kernel"], conf["vocab_size"],
                conf["num_hidden_layers"], conf["layer_norm_epsilon"],
                conf["mixer_rms_eps"], conf["tie_word_embeddings"])


def program_sizes(conf: dict) -> dict:
    """The serving program's own fields for the sizes this file states
    (``ArchConfig.scaled``), so the program runs the configuration as
    the file gives it."""
    m = dims(conf)
    if m.di % m.d:
        raise ValueError(f"intermediate_size {m.di} is no multiple of "
                         f"hidden_size {m.d}")
    return {"n_layers": m.layers, "d_model": m.d, "d_inner_mult": m.di // m.d,
            "ssm_state": m.st, "dt_rank": m.rank, "conv_width": m.cw,
            "vocab": m.vocab, "norm_eps": m.eps,
            "bcdt_rms_eps": m.mixer_eps, "tie_embeddings": m.tied,
            "residual_f32": bool(conf["residual_in_fp32"])}


def _dense(words, leaf, layer, n_in, n_out):
    return W.uniform(words, leaf, layer, (n_in, n_out),
                     1.0 / math.sqrt(n_in)).astype(jnp.bfloat16)


def _norm(words, leaf, layer, n):
    return W.uniform(words, leaf, layer, (n,), NORM_STD)


def _pick(words, leaf, layer, n, table):
    idx = (W.unit(words, leaf, layer, (n,)) * len(table)).astype(jnp.int32)
    return jnp.asarray(table)[idx]


def layer_tree(m: Dims, words, layer) -> dict:
    """One layer's weights in the serving layout and dtypes."""
    di = m.di
    return {
        "ln1": _norm(words, LN1, layer, m.d),
        "mixer": {
            "in_proj": _dense(words, W_IN, layer, m.d, 2 * di),
            "conv_w": W.uniform(words, CONV_W, layer, (m.cw, di),
                                1.0 / math.sqrt(m.cw)).astype(jnp.bfloat16),
            "conv_b": W.uniform(words, CONV_B, layer, (di,), CONV_B_STD),
            "x_proj": _dense(words, W_X, layer, di, m.rank + 2 * m.st),
            "dt_proj": _dense(words, W_DT, layer, m.rank, di),
            "dt_bias": _pick(words, DT_BIAS, layer, di, DT_BIAS_TABLE),
            "a_log": jnp.broadcast_to(jnp.asarray(np.log(np.arange(
                1, m.st + 1, dtype=np.float32))), (di, m.st)),
            "d_skip": _pick(words, D_SKIP, layer, di, D_TABLE),
            "out_proj": _dense(words, W_OUT, layer, di, m.d),
        },
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


def _rms(v, eps):
    return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)


def selective_scan(dt, A, B, C, x):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t from h_0 = 0, and
    y_t = h_t . C_t, one step at a time.  dt, x: (b, s, di); B, C:
    (b, s, st); A: (di, st) -> y (b, s, di)."""
    def step(h, t):
        dt_t, b_t, c_t, x_t = t
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1)
    h0 = jnp.zeros(x.shape[:1] + A.shape, jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(v, 0, 1)
                                        for v in (dt, B, C, x)))
    return jnp.swapaxes(y, 0, 1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(m: Dims, precision: str, x, w):
    s = x.shape[1]
    h = K.rmsnorm(x, 1.0 + w["ln1"], m.eps)
    a = w["mixer"]
    xz = K.matmul(h, a["in_proj"], precision)
    xs, z = xz[..., :m.di], xz[..., m.di:]
    pad = jnp.pad(xs, ((0, 0), (m.cw - 1, 0), (0, 0)))
    cw = a["conv_w"].astype(jnp.float32)
    xs = K.silu(sum(pad[:, i:i + s] * cw[i] for i in range(m.cw))
                + a["conv_b"])
    proj = K.matmul(xs, a["x_proj"], precision)
    r, B, C = (_rms(v, m.mixer_eps) for v in jnp.split(
        proj, [m.rank, m.rank + m.st], axis=-1))
    dt = jax.nn.softplus(K.matmul(r, a["dt_proj"], precision) + a["dt_bias"])
    y = selective_scan(dt, -jnp.exp(a["a_log"]), B, C, xs)
    y = (y + xs * a["d_skip"]) * K.silu(z)
    return x + K.matmul(y, a["out_proj"], precision)


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(m: Dims, words, layer):
    return layer_tree(m, words, layer)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _embed(m: Dims, leaf: int, words):
    return _table(words, leaf, m)


@functools.partial(jax.jit, static_argnums=0)
def _embed_rows(m: Dims, words, tokens):
    return jnp.take(_table(words, EMBED, m), tokens, axis=0).astype(
        jnp.float32)


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
        return _head_logits(precision, rows, self._head)


@functools.partial(jax.jit, static_argnums=0)
def _head_logits(precision: str, rows, head):
    return K.matmul(rows, head.T, precision)


def shapes(conf: dict) -> dict:
    """What the work counts need: the layers, the selective scan's sizes
    and the parameters a token passes through (each layer's four
    projections and its convolution; the output head counts, the
    embedding lookup does not).  No attention and no MLP."""
    m = dims(conf)
    layer = (m.d * 2 * m.di + m.cw * m.di + m.di * (m.rank + 2 * m.st)
             + m.rank * m.di + m.di * m.d)
    return {"layers": m.layers, "attention": None, "mlp": None,
            "ssm": {"layers": m.layers, "d_inner": m.di, "state": m.st,
                    "dt_rank": m.rank},
            "active_params": m.layers * layer + m.vocab * m.d}
