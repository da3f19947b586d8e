"""Serving-engine tests: slot-reuse hygiene, admission ordering,
ragged-prefill interleave determinism, and Pallas-vs-jnp parity.

The engines sample greedily, so every property here is asserted as
bit-identical token sequences — not allclose.  The reference for a
request is always the same request run in isolation (batch-1 prefill +
decode loop): continuous batching, chunked prefill, paged KV, and the
Pallas kernels must not change a single argmax.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_arch
from repro.launch.serve import (ContinuousEngine, Request, ServeEngine,
                                _merge_slot)
from repro.model import pallas_mode
from repro.model import transformer as T

CFG = get_arch("granite_3_2b").smoke()


@functools.lru_cache(maxsize=1)
def params():
    return T.init_params(jax.random.PRNGKey(0), CFG)


def prompt(seed: int, plen: int):
    return jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(7),
                                                 seed),
                              (1, plen), 2, CFG.vocab)


def solo_greedy(pr, gen: int, max_len: int):
    """Reference: the request alone in a batch-1 alternating engine."""
    eng = ServeEngine(CFG, params(), 1, max_len)
    req = Request(0, pr)
    eng.admit(req, slot=0)
    for _ in range(gen - 1):
        eng.step()
    return req.generated


def run_continuous(prompts, gen, max_len, batch, **kw):
    eng = ContinuousEngine(CFG, params(), batch, max_len, max_new=gen, **kw)
    reqs = [Request(i, p) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return eng, reqs


# ---------------------------------------------------------------------------
# ServeEngine slot reuse (the admit cache-merge regression)
# ---------------------------------------------------------------------------

def test_admit_slot_reuse_zeroes_stale_rows():
    """Two sequential requests through one slot: the second must see a
    slot wiped of the first occupant's KV rows.  The old shape-heuristic
    merge (`bdim is None` silent skip) left request A's decode rows in
    the gap between B's prompt and the shared max(lengths) mask, which
    B then attended."""
    plen, j, k, max_len = 8, 4, 4, 32
    eng = ServeEngine(CFG, params(), 2, max_len)
    a, long_req = Request(0, prompt(1, plen)), Request(1, prompt(2, plen))
    eng.admit(a, slot=0)
    eng.admit(long_req, slot=1)
    for _ in range(j):
        eng.step()           # A's decode writes rows [plen, plen+j)
    a.done = True
    b = Request(2, prompt(3, plen))
    eng.admit(b, slot=0)     # reuse: must zero slot 0 first

    # structural check: every slot-0 cache row past B's prompt is zero,
    # while slot 1 still holds its occupant's rows there
    for entry in eng.cache["slots"]:
        kc = entry["k"]      # (repeats, batch, S, hkv, hd)
        assert not jnp.any(kc[:, 0, plen:])
        assert jnp.any(kc[:, 1, plen:plen + j])

    for _ in range(k):
        eng.step()

    # bit-identical reference: B prefilled into a fresh slot, decoding
    # behind the same shared mask trajectory (slot 1 is j tokens ahead,
    # so B attends j zero rows it never wrote — same as in the engine)
    logits, pre = jax.jit(lambda p, t: T.prefill(p, CFG, t))(params(),
                                                             b.prompt)
    cache = _merge_slot(T.init_cache(CFG, 1, max_len), pre, 0)
    toks = [int(jnp.argmax(logits[0]))]
    step = jax.jit(lambda p, t, c, n: T.decode_step(p, CFG, t, c, n))
    for t in range(k):
        lg, cache = step(params(), jnp.asarray([[toks[-1]]], jnp.int32),
                         cache, jnp.int32(plen + j + t))
        toks.append(int(jnp.argmax(lg[0])))
    assert b.generated == toks


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


def test_continuous_matches_alternating():
    """Equal-length batch: the continuous engine and the alternating
    baseline agree token for token (the bench's identity gate)."""
    gen, max_len, plen, batch = 6, 48, 16, 3
    prompts = [prompt(30 + i, plen) for i in range(batch)]
    base = ServeEngine(CFG, params(), batch, max_len)
    base_reqs = [Request(i, p) for i, p in enumerate(prompts)]
    for i, r in enumerate(base_reqs):
        base.admit(r, slot=i)
    for _ in range(gen - 1):
        base.step()
    _, cont_reqs = run_continuous(prompts, gen, max_len, batch=batch,
                                  chunk=8)
    assert [r.generated for r in cont_reqs] == \
        [r.generated for r in base_reqs]


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
