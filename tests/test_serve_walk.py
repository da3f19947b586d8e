"""The serving layer walk writes only the K/V rows a step produced.

``serve_decode_step`` and ``chunk_step`` hand each attention layer its
K/V prefix and write the rows it returns in place, after the layer scan;
SSM layers still replace their whole state.  These tests hold that walk
to the reference paths bit for bit (``decode_step`` for decode, ``prefill``
for a chunk), check that nothing but the new rows changes in the
returned cache, and check the decode tick's program structure: no layer
scan output spans the cache's rows, and the compiled program needs less
scratch memory than one KV cache.
"""
from __future__ import annotations

import functools
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_arch
from repro.launch.serve import ContinuousEngine
from repro.model import transformer as T

# dense GQA; windowed local layers beside a global one, with a pattern
# period of 6 (8 layers: one scanned period and two tail layers); and
# Mamba layers with one attention layer per period of 8, MoE FFNs
CONFIGS = {
    "granite_3_2b": get_arch("granite_3_2b").smoke(),
    "gemma3_4b": replace(get_arch("gemma3_4b").smoke(), n_layers=8),
    "jamba_v0_1_52b": get_arch("jamba_v0_1_52b").smoke(),
}
MAX_LEN, KV = 40, 32


@functools.lru_cache(maxsize=None)
def params(name):
    return T.init_params(jax.random.PRNGKey(0), CONFIGS[name])


def filled_cache(cfg, batch):
    """A cache of random rows and states, so a write in the wrong place
    or a row left out shows."""
    leaves, tree = jax.tree.flatten(T.init_cache(cfg, batch, MAX_LEN))
    key = jax.random.PRNGKey(1)
    return jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.fold_in(key, i), v.shape).astype(v.dtype)
        for i, v in enumerate(leaves)])


def rows_axis(path) -> int:
    """The row axis of a K/V leaf: stacked "slots" entries carry the
    layer on axis 0."""
    return 2 if path[0].key == "slots" else 1


def is_kv(path) -> bool:
    return path[-1].key in ("k", "v")


def prefix_of(cache, kv_len):
    """The cache cut to its first ``kv_len`` rows: the reference steps then
    attend over the same rows as the paged ones."""
    return jax.tree_util.tree_map_with_path(
        lambda path, v: jax.lax.slice_in_dim(v, 0, kv_len,
                                             axis=rows_axis(path))
        if is_kv(path) else v, cache)


def assert_same_except(new, old, written):
    """``new`` equals ``old`` bit for bit wherever ``written(path)`` (a
    boolean mask broadcastable to the leaf) is False."""
    def check(path, n, o):
        assert bool(jnp.all((n == o) | written(path))), \
            jax.tree_util.keystr(path)
    jax.tree_util.tree_map_with_path(check, new, old)


@pytest.mark.parametrize("name", CONFIGS)
def test_serve_decode_step_matches_legacy_decode(name):
    """Ragged lengths and a mixed active mask: every slot's logits are
    bit-identical to the reference ``decode_step`` of that slot alone, each
    slot's K/V row lands at its own length in every layer (inactive
    slots too, as before) with the reference's values, inactive slots' SSM
    states are untouched, and nothing else in the cache moves."""
    cfg, p = CONFIGS[name], params(name)
    cache = filled_cache(cfg, 3)
    lengths = jnp.array([5, 17, 30], jnp.int32)
    active = jnp.array([True, False, True])
    tok = jax.random.randint(jax.random.PRNGKey(2), (3, 1), 2, cfg.vocab)
    logits, new = jax.jit(T.serve_decode_step, static_argnums=(1, 6))(
        p, cfg, tok, cache, lengths, active, KV)
    ref_step = jax.jit(T.decode_step, static_argnums=(1,))
    for i in range(3):
        old = prefix_of(T.cache_slot_view(cache, i), KV)
        lg, ref = ref_step(p, cfg, tok[i:i + 1], old, lengths[i])
        assert jnp.array_equal(logits[i], lg[0]), f"slot {i}"
        got = prefix_of(T.cache_slot_view(new, i), KV)

        def same(path, g, r, o):
            if is_kv(path):
                return jnp.array_equal(g, r)
            # an inactive slot keeps its SSM states; an active slot's are
            # not held to the one-slot reference run, whose float32 update
            # rounds differently from a batch of three in the last bit
            return bool(active[i]) or jnp.array_equal(g, o)
        assert all(jax.tree.leaves(jax.tree_util.tree_map_with_path(
            same, got, ref, old))), f"slot {i}"

    def written(path):
        if not is_kv(path):
            return jnp.ones((), bool)
        rows = jnp.arange(MAX_LEN)[None, :] == lengths[:, None]  # (b, S)
        return rows.reshape((1,) * (rows_axis(path) - 1) + rows.shape
                            + (1, 1))
    assert_same_except(new, cache, written)


@pytest.mark.parametrize("name", CONFIGS)
def test_chunk_step_writes_only_its_rows(name):
    """A prefill chunk at a nonzero offset changes only the chunk's K/V
    rows and the SSM states, and lands every row and state exactly as
    the same chunk landed through a cache that ends at ``kv_len`` — the
    rows past the prefix are never read or written."""
    cfg, p = CONFIGS[name], params(name)
    cache = filled_cache(cfg, 1)
    off, c = 16, 8
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, c), 2, cfg.vocab)
    step = jax.jit(T.chunk_step, static_argnums=(1, 5))
    logits, new = step(p, cfg, toks, cache, jnp.int32(off), KV)
    short_logits, short = step(p, cfg, toks, prefix_of(cache, KV),
                               jnp.int32(off), KV)
    assert jnp.array_equal(logits, short_logits)
    assert jax.tree.all(jax.tree.map(jnp.array_equal,
                                     prefix_of(new, KV), short))

    def written(path):
        if not is_kv(path):
            return jnp.ones((), bool)
        rows = (jnp.arange(MAX_LEN) >= off) & (jnp.arange(MAX_LEN) < off + c)
        return rows.reshape((1,) * rows_axis(path) + rows.shape + (1, 1))
    assert_same_except(new, cache, written)


def test_chunk_step_matches_legacy_prefill():
    """Two prefill chunks, the second at a nonzero offset, end in the
    logits and K/V rows of the reference whole-prompt ``prefill``."""
    cfg, p = CONFIGS["granite_3_2b"], params("granite_3_2b")
    plen, off = 24, 16
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, plen), 2, cfg.vocab)
    step = jax.jit(T.chunk_step, static_argnums=(1, 5))
    cache = T.init_cache(cfg, 1, MAX_LEN)
    _, cache = step(p, cfg, toks[:, :off], cache, jnp.int32(0), off)
    logits, cache = step(p, cfg, toks[:, off:], cache, jnp.int32(off), KV)
    ref_logits, ref = jax.jit(T.prefill, static_argnums=(1,))(p, cfg, toks)
    assert jnp.array_equal(logits[0, -1], ref_logits[0])
    for entry, r in zip(cache["slots"], ref["slots"]):
        for n in ("k", "v"):
            assert jnp.array_equal(entry[n][:, :, :plen], r[n]), n
            assert not jnp.any(entry[n][:, :, plen:])


# ---------------------------------------------------------------------------
# structure of the decode tick
# ---------------------------------------------------------------------------

# rows per slot unlike any other dimension of the program; float32, so
# the CPU backend does not widen the bf16 cache to a float32 copy of its
# own (a CPU artifact that would hide the walk's scratch)
STRUCT_MAX_LEN = 200


@functools.lru_cache(maxsize=1)
def decode_program():
    cfg = replace(CONFIGS["granite_3_2b"], dtype="float32")
    eng = ContinuousEngine(cfg, T.init_params(jax.random.PRNGKey(0), cfg), 4,
                           STRUCT_MAX_LEN, chunk=8, page=8, max_new=4)
    return eng, (eng.params, eng.cache, eng.dev, eng._active), 16


def scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from scans(sub)


def test_decode_tick_layer_scan_outputs_no_cache_rows():
    """No output of the decode tick's layer scan spans ``max_len`` rows:
    the scan hands back the new rows, never a whole-layer cache."""
    eng, args, kv = decode_program()
    jaxpr = jax.make_jaxpr(eng._decode, static_argnums=(4,))(*args, kv)
    found = list(scans(jaxpr.jaxpr))
    assert found
    for eqn in found:
        for out in eqn.outvars:
            assert STRUCT_MAX_LEN not in out.aval.shape, out.aval


def test_decode_tick_scratch_is_below_one_kv_cache():
    """The compiled decode tick needs less scratch memory than one KV
    cache: the cache is updated in place, not rebuilt beside itself."""
    eng, args, kv = decode_program()
    stats = eng._decode.lower(*args, kv=kv).compile().memory_analysis()
    cache_bytes = sum(v.nbytes for v in jax.tree.leaves(eng.cache))
    assert stats.temp_size_in_bytes < cache_bytes, \
        (stats.temp_size_in_bytes, cache_bytes)
