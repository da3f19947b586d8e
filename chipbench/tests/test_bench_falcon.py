"""The falcon_mamba_7b reference against the serving program at a small
width, and the small bursty cell run whole on the CPU.

Prefill goes in chunks through the fused ``scan_gate`` kernel
(interpreted on the CPU), decoding through the cache; logits are
compared at every position.  Tolerance, as for granite
(``test_bench_reference.py``): the program keeps weights and the
activations the products take in bfloat16 (a relative step of 2**-8),
where the reference keeps float32 (the residual stream is float32 in
both, as the source sets ``residual_in_fp32``); at this
width and depth the logits agree to within 3 % of the largest logit
(about 1 % at this seed).  The program without the B/C/Δ norm misses by
far more (about 50 %), and so does the float8 control (about 20 %).
"""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import spec
from test_bench_reference import _program_logits, _setup

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REL_TOL = 0.03
SEED = 2 ** 31 + 77


def _cell():
    return {"name": "falcon_burst", "chips": 1,
            "conf": spec.load_json(os.path.join(DATA, "tiny_falcon.json")),
            "mix": spec.load_json(os.path.join(DATA, "tiny_burst.json")),
            "ref": spec.load_module(
                os.path.join(spec.BENCH_DIR, "reference",
                             "falcon_mamba_7b.py"),
                "chipbench_reference_tiny_falcon"),
            "end_to_end": [], "per_layer": [], "readers": {}}


def _relative_errors(bcdt_rms_eps=None):
    """Largest |program - reference| logit over prefill and over decode
    positions, and the float8 control's, each over the largest
    reference logit."""
    conf, ref, cfg = _setup("tiny_falcon", "falcon_mamba_7b")
    if bcdt_rms_eps is not None:
        cfg = cfg.scaled(bcdt_rms_eps=bcdt_rms_eps)
    params = ref.make_params(conf, SEED)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(2, conf["vocab_size"], (1, 64)),
                         jnp.int32)
    got, toks = _program_logits(cfg, params, prompt, 8, 32)
    seq = np.concatenate([np.asarray(prompt)[0], toks])[None]
    r = ref.Reference(conf, SEED)
    want = np.asarray(r.logits(r.hidden(seq, "f32")[0], "f32"))
    scale = np.abs(want).max()
    err = np.abs(got - want).max(axis=-1) / scale
    ctl = np.asarray(r.logits(r.hidden(seq, "fp8")[0], "fp8"))
    return err[:64].max(), err[64:].max(), np.abs(ctl - want).max() / scale


def test_reference_matches_prefill_and_decode():
    prefill, decode, control = _relative_errors()
    assert prefill <= REL_TOL, ("prefill", prefill)
    assert decode <= REL_TOL, ("decode", decode)
    # the float8 control departs by more than the tolerance
    assert control > REL_TOL


def test_program_without_the_bcdt_norm_fails_the_tolerance():
    prefill, decode, _ = _relative_errors(bcdt_rms_eps=0.0)
    assert prefill > REL_TOL and decode > REL_TOL, (prefill, decode)


def test_weights_are_the_same_made_whole_or_by_layer():
    conf, ref, _ = _setup("tiny_falcon", "falcon_mamba_7b")
    from harness import weights as W
    whole = ref.make_params(conf, SEED)["decoder"]["slots"][0]
    m = ref.dims(conf)
    for layer in (0, 1):
        one = ref.layer_tree(m, W.seed_words(SEED), jnp.uint32(layer))
        for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(one)):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a[layer], np.float32),
                                  np.asarray(b, np.float32))


def test_published_widths_and_active_parameters():
    """The configuration as run keeps every published width of the
    program's falcon_mamba_7b and cuts only the depth; a token passes
    32 layers of 105,152,512 projection and convolution weights and
    the 65024 x 4096 head."""
    from repro.configs.registry import get_arch
    conf = spec.load_json(os.path.join(spec.BENCH_DIR, "configs",
                                       "falcon_mamba_7b.json"))
    ref = spec.load_module(os.path.join(spec.BENCH_DIR, "reference",
                                        "falcon_mamba_7b.py"),
                           "chipbench_reference_falcon_widths")
    cfg = get_arch(conf["program_arch"]).scaled(**ref.program_sizes(conf))
    pub = get_arch(conf["program_arch"])
    for k in ("d_model", "d_inner", "ssm_state", "dt_rank_", "conv_width",
              "vocab", "norm_eps", "bcdt_rms_eps"):
        assert getattr(cfg, k) == getattr(pub, k), k
    assert (cfg.n_layers, pub.n_layers) == (32, 64)
    assert conf["reduced"] == ["num_hidden_layers"]
    assert conf["residual_in_fp32"] and cfg.residual_f32
    sh = ref.shapes(conf)
    assert sh["attention"] is None and sh["mlp"] is None
    assert sh["active_params"] == 32 * 105_152_512 + 65024 * 4096 \
        == 3_631_218_688


def test_tiny_cell_end_to_end_is_correct():
    from harness import cell as C
    res = C.run(_cell(), 2 ** 31 + 3, 1.5, False, time.time(),
                check_device=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert res["checks"]["served_tokens_compared"]["value"] >= 10


@pytest.mark.parametrize("seed", [2 ** 31 + 11, 2 ** 31 + 12])
def test_float8_control_is_not_correct(seed):
    from harness import cell as C
    from harness import check as CK
    sess = C.Session(_cell(), seed, check_device=False)
    length, width = sess.mix["engine"]["max_len"], sess.out_max
    limit = sess.conf["correct"]["max_logit_gap"]
    win = sess.window(2.0, False)
    finite = sess.engine.logits_finite()
    done = C.finished(win)
    picked = CK.sample(done, len(done), seed)
    ref = sess.ref.Reference(sess.conf, seed)
    prog = CK.gaps(ref, picked, length, width)
    ctl = CK.gaps(ref, picked, length, width, precision="fp8",
                  pick_own=True)
    assert prog.size == ctl.size > 0
    assert C.passes(C.checks_of(sess, win, prog, limit, finite))
    assert not C.passes(C.checks_of(sess, win, ctl, limit, finite))
