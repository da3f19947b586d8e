"""Serving-engine tests: slot-reuse hygiene, admission ordering,
ragged-prefill interleave determinism, and Pallas-vs-jnp parity.

The engine samples greedily, so every property here is asserted as
bit-identical token sequences — not allclose.  The reference for a
request is always the same request run in isolation (:func:`solo_greedy`:
batch-1 ``prefill`` of its prompt, then a ``decode_step`` loop):
continuous batching, chunked prefill, paged KV, and the Pallas kernels
must not change a single argmax.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.launch.serve import ContinuousEngine, Request
from repro.model import pallas_mode
from repro.model import transformer as T

# dense GQA, and pure Mamba (no K/V: conv and SSM states only)
CONFIGS = {name: get_arch(name).smoke()
           for name in ("granite_3_2b", "falcon_mamba_7b")}
CFG = CONFIGS["granite_3_2b"]


@functools.lru_cache(maxsize=None)
def params(name: str = "granite_3_2b"):
    return T.init_params(jax.random.PRNGKey(0), CONFIGS[name])


def prompt(seed: int, plen: int, vocab: int = CFG.vocab):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(7),
                                                 seed),
                              (1, plen), 2, vocab)


@functools.lru_cache(maxsize=None)
def reference_steps(name: str):
    cfg = CONFIGS[name]
    return (jax.jit(lambda p, t: T.prefill(p, cfg, t)),
            jax.jit(lambda p, t, c, n: T.decode_step(p, cfg, t, c, n)))


def solo_greedy(pr, gen: int, max_len: int, name: str = "granite_3_2b"):
    """Reference: the request alone at batch 1 — ``prefill`` of its
    prompt into the first rows of a fresh cache, then a greedy
    ``decode_step`` loop."""
    prefill, step = reference_steps(name)
    logits, pre = prefill(params(name), pr)
    cache = jax.tree.map(
        lambda c, v: jax.lax.dynamic_update_slice(c, v.astype(c.dtype),
                                                  (0,) * c.ndim),
        T.init_cache(CONFIGS[name], 1, max_len), pre)
    toks = [int(jnp.argmax(logits[0]))]
    for n in range(pr.shape[1], pr.shape[1] + gen - 1):
        logits, cache = step(params(name), jnp.asarray([[toks[-1]]]),
                             cache, jnp.int32(n))
        toks.append(int(jnp.argmax(logits[0])))
    return toks


def run_continuous(prompts, gen, max_len, batch, **kw):
    eng = ContinuousEngine(CFG, params(), batch, max_len, max_new=gen, **kw)
    reqs = [Request(i, p) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs


def slot_rows(cache, i):
    """Batch slot ``i`` of every cache leaf, on the host ("slots"
    entries carry batch on axis 1, "tail" entries on axis 0)."""
    return {"slots": [jax.tree.map(lambda v: np.asarray(v[:, i]), c)
                      for c in cache["slots"]],
            "tail": [jax.tree.map(lambda v: np.asarray(v[i]), c)
                     for c in cache["tail"]]}


# ---------------------------------------------------------------------------
# slot reuse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", CONFIGS)
def test_admit_slot_reuse_zeroes_stale_rows(name):
    """A request admitted into a slot a finished request used finds it
    wiped: every K/V row, conv tail and SSM state of that slot is zero,
    the other slot's in-flight rows are untouched, and the new request
    then generates its solo reference tokens."""
    cfg, plen, max_len = CONFIGS[name], 8, 32
    eng = ContinuousEngine(cfg, params(name), 2, max_len, chunk=8,
                           max_new=8)
    a = Request(0, prompt(1, plen, cfg.vocab), max_new=3)
    busy = Request(1, prompt(2, plen, cfg.vocab), max_new=8)
    for r in (a, busy):
        eng.submit(r)
    while not a.done:
        eng.tick()
    assert not busy.done
    before = slot_rows(eng.cache, 1)
    assert any(np.any(v) for v in jax.tree.leaves(slot_rows(eng.cache, 0)))

    b = Request(2, prompt(3, plen, cfg.vocab), max_new=4)
    eng.submit(b)
    eng._admit_free_slots()
    assert eng.slots[0] is b
    assert not any(np.any(v) for v in jax.tree.leaves(slot_rows(eng.cache, 0)))
    jax.tree.map(np.testing.assert_array_equal, slot_rows(eng.cache, 1),
                 before)

    eng.run()
    assert b.generated == solo_greedy(b.prompt, 4, max_len, name)


# ---------------------------------------------------------------------------
# continuous engine: ordering, determinism, parity
# ---------------------------------------------------------------------------

def test_admission_ordering_and_slot_recycling():
    """FIFO admission through fewer slots than requests: every request
    completes with its full budget, and identical prompts produce
    identical tokens whether served in the first wave or after a slot
    was recycled."""
    gen, max_len = 6, 32
    prompts = [prompt(1, 8), prompt(2, 8), prompt(3, 8),
               prompt(1, 8), prompt(2, 8)]
    eng, reqs = run_continuous(prompts, gen, max_len, batch=2, chunk=8)
    assert all(r.done for r in reqs)
    assert [len(r.generated) for r in reqs] == [gen] * 5
    assert eng.state == [0, 0] and not eng.queue
    # same prompt, one served through a recycled slot: same tokens
    assert reqs[0].generated == reqs[3].generated
    assert reqs[1].generated == reqs[4].generated
    assert reqs[0].generated != reqs[1].generated


def test_ragged_prefill_interleave_determinism():
    """Ragged prompt lengths under chunked prefill: each request's
    tokens are bit-identical to the request run alone — the interleave
    (whose chunk lands on which tick, which slots decode beside it)
    must be invisible — and a reset re-run reproduces them exactly."""
    gen, max_len, chunk = 6, 48, 8
    plens = [7, 19, 13]
    prompts = [prompt(i + 10, pl) for i, pl in enumerate(plens)]
    eng, reqs = run_continuous(prompts, gen, max_len, batch=2, chunk=chunk)
    for r, pl in zip(reqs, plens):
        assert r.generated == solo_greedy(r.prompt, gen, max_len), \
            f"request with plen={pl} diverged under interleaving"
    first = [r.generated for r in reqs]
    eng.reset()
    reqs2 = [Request(i, p) for i, p in enumerate(prompts)]
    for r in reqs2:
        eng.submit(r)
    eng.run()
    assert [r.generated for r in reqs2] == first


def test_pallas_parity_bit_identical():
    """The Pallas fast path (flash attention on prefill chunks, planned
    matmul in the MLP) generates bit-identical greedy tokens to the jnp
    path on the smoke config.  Thresholds are lowered so the tiny test
    shapes actually route through the kernels."""
    gen, max_len, chunk = 5, 48, 16
    prompts = [prompt(21, 32), prompt(22, 32)]
    _, jnp_reqs = run_continuous(prompts, gen, max_len, batch=2,
                                 chunk=chunk)
    _, pl_reqs = run_continuous(
        prompts, gen, max_len, batch=2, chunk=chunk, use_pallas=True,
        pallas_opts=dict(min_attn_q=16, min_matmul_rows=16))
    pallas_mode.configure(enabled=False)
    assert [r.generated for r in pl_reqs] == \
        [r.generated for r in jnp_reqs]


def test_equal_length_batch_matches_solo():
    """Equal-length batch: each request's tokens from the continuous
    engine equal its solo reference token for token (the bench's
    identity gate)."""
    gen, max_len, plen, batch = 6, 48, 16, 3
    prompts = [prompt(30 + i, plen) for i in range(batch)]
    _, reqs = run_continuous(prompts, gen, max_len, batch=batch, chunk=8)
    for r in reqs:
        assert r.generated == solo_greedy(r.prompt, gen, max_len)


def test_submit_validation():
    eng = ContinuousEngine(CFG, params(), 1, 16, max_new=4)
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(0, prompt(1, 16)))
    with pytest.raises(ValueError, match="exceeds token buffer"):
        eng.submit(Request(1, prompt(1, 4), max_new=12))


def test_admission_is_stamped_between_submit_and_first_token():
    """Each request's admission stamp falls between its submission and
    its first token, also for requests that waited for a slot."""
    prompts = [prompt(i, 8) for i in range(4)]
    _, reqs = run_continuous(prompts, 4, 32, batch=2, chunk=8, sync=True)
    assert all(r.done for r in reqs)
    for r in reqs:
        assert 0 < r.t_submit <= r.t_admit <= r.t_first, r


def test_chunk_program_carries_named_scopes():
    """The prefill-chunk tick program's operations carry the model's
    named scopes in their name stacks, which the profiler's trace
    keeps for each device operation."""
    eng = ContinuousEngine(CFG, params(), 2, 32, chunk=8, max_new=4)
    text = eng.lower_chunk(8, 16).as_text(debug_info=True)
    for name in ("embed", "layer", "attention", "mlp", "kv_cache", "head",
                 "sample"):
        # a component of some operation's name stack (a location)
        assert re.search(rf'loc\("([^"]*/)?{name}/', text), name


def test_mamba_chunked_prefill_state_carry():
    """Chunked prefill of a Mamba arch matches whole-prompt prefill:
    the conv tail + hidden-state carry across chunks is exact on the
    jnp path (bit-identical logits); the fused scan+gate kernel
    accumulates y = h·C in a different f32 order, so it is held to a
    bf16-ULP tolerance instead (its f32 exactness is pinned by
    ``kernels/bench.py --smoke``)."""
    cfg = get_arch("falcon_mamba_7b").smoke()
    p = T.init_params(jax.random.PRNGKey(1), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 2, cfg.vocab)
    logits_full, _ = jax.jit(lambda pp, t: T.prefill(pp, cfg, t))(p, toks)

    def chunked(enabled):
        with pallas_mode.pallas_mode(enabled=enabled, min_scan_seq=8,
                                     min_attn_q=8):
            cache = T.init_cache(cfg, 1, 32)
            step = jax.jit(
                lambda pp, t, c, off: T.chunk_step(pp, cfg, t, c, off, 32),
                static_argnames=())
            _, cache = step(p, toks[:, :8], cache, jnp.int32(0))
            lg, _ = step(p, toks[:, 8:], cache, jnp.int32(8))
        return lg[:, -1]

    assert jnp.array_equal(chunked(False), logits_full)
    fused = chunked(True).astype(jnp.float32)
    assert jnp.allclose(fused, logits_full.astype(jnp.float32),
                        rtol=0.02, atol=0.02)
