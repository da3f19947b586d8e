"""SwiGLU MLP and Mixture-of-Experts.

MoE uses top-k token-choice routing with a capacity-bounded one-hot
dispatch (einsum form): the dispatch tensors shard over the expert axis
(`model` mesh axis), which keeps the per-chip footprint at
tokens × experts/chips × capacity. An all-to-all materializes in the
HLO when expert-parallel and data-parallel tokens exchange — exactly
the collective the roofline analysis tracks.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.registry import ArchConfig
from .layers import dense_init
from .sharding import shard_activation


def init_mlp(key, d_model: int, d_ff: int, dtype) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


@jax.named_scope("mlp")
def mlp(p, x):
    from .pallas_mode import mode
    md = mode()
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    if md.enabled and rows >= md.min_matmul_rows:
        # PolyTOPS-planned matmul kernel: worth it once the token count
        # amortizes the grid (below the threshold one XLA dot wins)
        from ..kernels import ops
        x2 = x.reshape(rows, x.shape[-1])
        h = jax.nn.silu(ops.matmul(x2, p["w_gate"])) * ops.matmul(x2, p["w_up"])
        h = h.reshape(x.shape[:-1] + (h.shape[-1],))
        h = shard_activation(h, ("batch", "seq", "ffn"))
        return ops.matmul(h.reshape(rows, -1),
                          p["w_down"]).reshape(x.shape)
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = shard_activation(h, ("batch", "seq", "ffn"))
    return h @ p["w_down"]


def init_moe(key, cfg: ArchConfig, dtype) -> Dict:
    e = cfg.n_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    d, f = cfg.d_model, cfg.d_ff
    scale = 1.0 / (d ** 0.5)
    p = {
        "router": dense_init(k1, d, e, jnp.float32),
        "w_gate": (jax.random.normal(k2, (e, d, f), jnp.float32) * scale).astype(dtype),
        "w_up": (jax.random.normal(k3, (e, d, f), jnp.float32) * scale).astype(dtype),
        "w_down": (jax.random.normal(k4, (e, f, d), jnp.float32) * (f ** -0.5)).astype(dtype),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(k5, d, f, dtype)
    return p


@jax.named_scope("moe")
def moe(p, cfg: ArchConfig, x, capacity_factor: float = 1.25
        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Grouped token-choice MoE. Returns (output, aux_loss). x: (b, s, d).

    Tokens are split into G routing groups (G = DP shard count, installed
    by the launcher): each group routes its own tokens with a per-group
    capacity, so dispatch tensors are (G, t/G, e, cap_g) — linear in
    tokens — and the group↔expert exchange lowers to an all-to-all
    between the DP and expert-parallel ('model') mesh axes.
    """
    from .sharding import moe_groups
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    g = moe_groups()
    if t % g or (t // g) < 1:
        g = 1
    tg = t // g
    xt = x.reshape(g, tg, d)
    xt = shard_activation(xt, ("batch", None, None))
    logits = (xt.astype(jnp.float32) @ p["router"])            # (g, tg, e)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (g, tg, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = max(int(capacity_factor * tg * k / e) + 3 & ~3, 4)
    # position of each (token, k) slot within its expert queue (per group)
    oh = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)          # (g, tg, k, e)
    pos_in_e = (jnp.cumsum(oh.reshape(g, tg * k, e), axis=1)
                - 1).reshape(g, tg, k, e)
    pos = jnp.sum(pos_in_e * oh, axis=-1)                      # (g, tg, k)
    keep = pos < cap
    disp4 = (jax.nn.one_hot(gate_idx, e, dtype=x.dtype)[..., None]
             * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                              dtype=x.dtype)[..., None, :])[..., :cap]
    comb4 = disp4 * gate_vals[..., None, None].astype(x.dtype)
    disp = disp4.sum(2)                                        # (g, tg, e, cap)
    comb = comb4.sum(2)
    disp = shard_activation(disp, ("batch", None, "experts", None))
    comb = shard_activation(comb, ("batch", None, "experts", None))

    xe = jnp.einsum("gtec,gtd->gecd", disp, xt)                # (g, e, cap, d)
    xe = shard_activation(xe, ("batch", "experts", None, None))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", xe, p["w_up"])
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])          # (g, e, cap, d)
    ye = shard_activation(ye, ("batch", "experts", None, None))
    out = jnp.einsum("gtec,gecd->gtd", comb, ye).reshape(b, s, d)

    if cfg.shared_expert:
        out = out + mlp(p["shared"], x)

    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(gate_idx[..., 0], e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x.dtype), aux
