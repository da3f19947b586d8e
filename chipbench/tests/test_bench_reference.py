"""The plain float32 references against the serving program at a small
width: prefill chunks through the Pallas kernels (interpreted on the
CPU) and decoding through the cache, logits at every position.

Tolerance: the program keeps weights and activations in bfloat16
(8 significant bits, a relative step of 2**-8) and rounds after every
layer; the reference keeps float32.  At this width and depth the logits
agree to within 3 % of the largest logit.  Stating a whole layer in the
wrong place, or skipping a norm, moves them by far more (checked below).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REL_TOL = 0.03
SEED = 2 ** 31 + 77


def _setup(conf_name, ref_name):
    from repro.configs.registry import get_arch
    conf = spec.load_json(os.path.join(DATA, conf_name + ".json"))
    ref = spec.load_module(os.path.join(spec.BENCH_DIR, "reference",
                                        ref_name + ".py"),
                           "chipbench_reference_test_" + ref_name)
    cfg = get_arch(conf["program_arch"]).scaled(**ref.program_sizes(conf))
    return conf, ref, cfg


def _program_logits(cfg, params, prompt, n_decode, chunk):
    """Program logits at every prompt position (chunked prefill, Pallas
    kernels) and at each of ``n_decode`` greedy decode steps."""
    from repro.model import pallas_mode
    from repro.model import transformer as T
    plen = prompt.shape[1]
    cache = T.init_cache(cfg, 1, 128)
    out = []
    with pallas_mode.pallas_mode(enabled=True, min_matmul_rows=chunk,
                                 min_attn_q=chunk, min_scan_seq=chunk):
        for off in range(0, plen, chunk):
            lg, cache = jax.jit(T.chunk_step, static_argnums=(1, 5))(
                params, cfg, prompt[:, off:off + chunk], cache,
                jnp.int32(off), 64)
            out.append(np.asarray(lg[0], np.float32))
        toks = []
        tok = int(np.argmax(out[-1][-1]))
        for i in range(n_decode):
            toks.append(tok)
            lg, cache = jax.jit(T.serve_decode_step, static_argnums=(1, 6))(
                params, cfg, jnp.array([[tok]], jnp.int32), cache,
                jnp.array([plen + i], jnp.int32), jnp.array([True]), 128)
            out.append(np.asarray(lg, np.float32))
            tok = int(np.argmax(out[-1][-1]))
    return np.concatenate(out), toks


@pytest.mark.parametrize("conf_name,ref_name", [
    ("tiny_granite", "granite_3_2b")])
def test_reference_matches_prefill_and_decode(conf_name, ref_name):
    conf, ref, cfg = _setup(conf_name, ref_name)
    params = ref.make_params(conf, SEED)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(2, conf["vocab_size"], (1, 64)),
                         jnp.int32)
    got, toks = _program_logits(cfg, params, prompt, 8, 32)
    seq = np.concatenate([np.asarray(prompt)[0], toks])[None]
    r = ref.Reference(conf, SEED)
    hid = r.hidden(seq, "f32")
    want = np.asarray(r.logits(hid[0], "f32"))
    scale = np.abs(want).max()
    err = np.abs(got - want).max(axis=-1)
    assert err[:64].max() <= REL_TOL * scale, ("prefill", err[:64].max(), scale)
    assert err[64:].max() <= REL_TOL * scale, ("decode", err[64:].max(), scale)
    # the float8 control departs by more than the tolerance
    ctl = np.asarray(r.logits(r.hidden(seq, "fp8")[0], "fp8"))
    assert np.abs(ctl - want).max() > REL_TOL * scale


def test_weights_are_the_same_made_whole_or_by_layer():
    conf, ref, _ = _setup("tiny_granite", "granite_3_2b")
    from harness import weights as W
    whole = ref.make_params(conf, SEED)["decoder"]["slots"][0]
    m = ref.dims(conf)
    one = ref.layer_tree(m, W.seed_words(SEED), jnp.uint32(1))
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(one)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a[1], np.float32),
                              np.asarray(b, np.float32))


def test_weights_follow_the_seed():
    conf, ref, _ = _setup("tiny_granite", "granite_3_2b")
    a = ref.make_params(conf, SEED)["embed"]
    b = ref.make_params(conf, SEED + 2 ** 32)["embed"]
    assert not np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
