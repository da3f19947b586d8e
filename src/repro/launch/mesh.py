"""Mesh construction + sharding policy for the production topology.

Single pod:  (data=16, model=16)          — 256 chips (TPU v5e pod slice)
Multi pod:   (pod=2, data=16, model=16)   — 512 chips

DP runs over ('pod','data'); TP/EP/vocab over 'model'. Parameters of
large archs additionally shard over 'data' (FSDP/ZeRO-3); optimizer
states inherit parameter specs (ZeRO-1 falls out for free).

Everything here is a FUNCTION of the mesh — importing this module never
touches jax device state.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from ..configs.registry import ArchConfig


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the layers annotate activations with
    # with_sharding_constraint, which Explicit axes (make_mesh's default
    # since JAX 0.8) refuse
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        out = 1
        for n in name:
            out *= mesh.shape[n]
        return out
    return mesh.shape[name]


def logical_rules(cfg: ArchConfig, mesh: Mesh, *, batch: int, seq_shard: bool = False
                  ) -> Dict[str, Any]:
    """Logical activation axis -> physical mesh axes for this arch."""
    model_n = mesh.shape["model"]
    dp = dp_axes(mesh)
    dp_n = axis_size(mesh, dp)
    rules: Dict[str, Any] = {
        "batch": dp if batch % dp_n == 0 else
                 ("data" if batch % mesh.shape["data"] == 0 else None),
        "seq": "model" if seq_shard else None,
        "heads": "model" if cfg.n_heads % model_n == 0 else None,
        "kv_heads": "model" if cfg.n_kv_heads % model_n == 0 else None,
        "ffn": "model" if (cfg.d_ff and cfg.d_ff % model_n == 0)
               or (cfg.family in ("ssm", "hybrid") and cfg.d_inner % model_n == 0)
               else None,
        "experts": "model" if cfg.n_experts and cfg.n_experts % model_n == 0 else None,
        "vocab": "model" if cfg.vocab % model_n == 0 else None,
    }
    return rules


# ---------------------------------------------------------------------------
# parameter shardings (by pytree path name conventions)
# ---------------------------------------------------------------------------

def _param_spec(path: str, leaf, cfg: ArchConfig, mesh: Mesh) -> P:
    model_n = mesh.shape["model"]
    # FSDP shards over the full DP domain (pod×data in multi-pod): more
    # shards AND consistent device order with the batch sharding (avoids
    # GSPMD "involuntary full rematerialization" reshards)
    data_ax = dp_axes(mesh) if cfg.fsdp else None
    heads_ok = cfg.n_heads % model_n == 0
    ff_ok = cfg.d_ff % model_n == 0 if cfg.d_ff else False
    di_ok = cfg.d_inner % model_n == 0
    exp_ok = cfg.n_experts % model_n == 0 if cfg.n_experts else False
    vocab_ok = cfg.vocab % model_n == 0

    def maybe(ax_ok, ax="model"):
        return ax if ax_ok else None

    name = path.split("/")[-1]
    ndim = leaf.ndim
    spec: Tuple = (None,) * ndim
    if name in ("embed", "lm_head"):
        spec = (maybe(vocab_ok), data_ax)
    elif name == "frontend_proj":
        spec = (data_ax, None)
    elif name == "wq":
        spec = (data_ax, maybe(heads_ok))
    elif name in ("wk", "wv"):
        kv_ok = cfg.n_kv_heads % model_n == 0
        spec = (data_ax, maybe(kv_ok))
    elif name == "wo":
        spec = (maybe(heads_ok), data_ax)
    elif name in ("w_gate", "w_up"):
        if "ffn" in path and cfg.n_experts and ndim == 3:   # MoE experts
            spec = (maybe(exp_ok), data_ax, None)
        else:
            spec = (data_ax, maybe(ff_ok))
    elif name == "w_down":
        if "ffn" in path and cfg.n_experts and ndim == 3:
            spec = (maybe(exp_ok), None, data_ax)
        else:
            spec = (maybe(ff_ok), data_ax)
    elif name == "router":
        spec = (None, maybe(exp_ok))
    elif name == "in_proj":
        spec = (data_ax, maybe(di_ok))
    elif name == "out_proj":
        spec = (maybe(di_ok), data_ax)
    elif name == "x_proj":
        spec = (maybe(di_ok), None)
    elif name == "dt_proj":
        spec = (None, maybe(di_ok))
    elif name in ("conv_w",):
        spec = (None, maybe(di_ok))
    elif name in ("a_log", "d_skip", "conv_b", "dt_bias"):
        spec = (maybe(di_ok),) + (None,) * (ndim - 1)
    else:   # norms & misc: replicated
        spec = (None,) * ndim
    spec = spec[:ndim] + (None,) * (ndim - len(spec))
    return P(*spec)


def _is_stacked(path_keys) -> bool:
    """Params under decoder/encoder 'slots' carry a leading layer axis."""
    return "slots" in path_keys


def param_pspecs(params, cfg: ArchConfig, mesh: Mesh):
    """Pytree of PartitionSpec matching `params`."""

    def spec_for(path, leaf):
        keys = [_key_str(k) for k in path]
        name = "/".join(keys)
        stacked = _is_stacked(keys)
        base = _param_spec(name, _LeafView(leaf, stacked), cfg, mesh)
        if stacked:
            return P(*((None,) + tuple(base)))
        return base

    return jax.tree_util.tree_map_with_path(spec_for, params)


class _LeafView:
    """Leaf with the stacked layer axis hidden."""

    def __init__(self, leaf, stacked: bool):
        self.ndim = leaf.ndim - (1 if stacked else 0)


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def shardings_for(tree_specs, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_param_handlers(cfg: ArchConfig, mesh: Mesh):
    """(gather_fn, grad_fn) for FSDP: see model.sharding.set_param_handlers.

    gather_fn re-constrains a *sliced per-layer* param tree to TP-only
    specs (data axis dropped) — the path names still match because only
    the leading 'slots' stacking is gone. grad_fn pins a full gradient
    tree to the FSDP param specs."""
    if not cfg.fsdp:
        return None, None
    tp_cfg = cfg.scaled(fsdp=False)

    def gather_fn(tree):
        def constrain(path, leaf):
            keys = [_key_str(k) for k in path]
            spec = _param_spec("/".join(keys), leaf, tp_cfg, mesh)
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, spec))
        return jax.tree_util.tree_map_with_path(constrain, tree)

    def grad_fn(tree):
        specs = param_pspecs(tree, cfg, mesh)
        return jax.tree.map(
            lambda leaf, s: jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, s)),
            tree, specs)

    return gather_fn, grad_fn


# ---------------------------------------------------------------------------
# cache shardings (decode)
# ---------------------------------------------------------------------------

def cache_pspecs(cache, cfg: ArchConfig, mesh: Mesh, batch: int):
    """KV caches: batch over DP when divisible; otherwise shard the
    sequence axis over 'model' (long-context decode, flash-decoding
    style distributed softmax). Mamba states: d_inner over 'model'."""
    dp = dp_axes(mesh)
    dp_n = axis_size(mesh, dp)
    model_n = mesh.shape["model"]
    batch_ax = dp if batch % dp_n == 0 else None
    kv_ok = cfg.n_kv_heads % model_n == 0
    di_ok = cfg.d_inner % model_n == 0

    def spec_for(path, leaf):
        keys = [_key_str(k) for k in path]
        stacked = "slots" in keys
        lead = (None,) if stacked else ()
        name = keys[-1]
        nd = leaf.ndim
        if name in ("k", "v"):
            # (b, S, hkv, hd): prefer head sharding; else shard S on model
            if kv_ok:
                spec = lead + (batch_ax, None, "model", None)
            else:
                spec = lead + (batch_ax, "model", None, None)
        elif name == "conv":
            spec = lead + (batch_ax, None, "model" if di_ok else None)
        elif name == "ssm":
            spec = lead + (batch_ax, "model" if di_ok else None, None)
        else:
            spec = (None,) * nd
        spec = tuple(spec)[:nd] + (None,) * (nd - len(spec))
        return P(*spec)

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def batch_pspec(mesh: Mesh, batch: int) -> P:
    dp = dp_axes(mesh)
    if batch % axis_size(mesh, dp) == 0:
        return P(dp)
    if batch % mesh.shape["data"] == 0:
        return P("data")
    return P(None)
