"""Attention-free stacks in the serving engine, and the shared
discretisation of the Mamba layers.

A stack with no attention layer reads no KV rows, so
``ContinuousEngine`` gives every tick one KV bound (``max_len``) and
compiles each tick kind once, where a stack with attention compiles one
program per page bound.  The one-token decode shares ``_ssm_inputs``
(and with it Falcon-Mamba's B/C/Δ norm) with prefill.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.core import akg
from repro.launch.serve import ContinuousEngine, Request, attends
from repro.model import ssm as SSM
from repro.model import transformer as T

FALCON = get_arch("falcon_mamba_7b").smoke()
GRANITE = get_arch("granite_3_2b").smoke()
TICKS = ("_decode", "_chunk", "_mixed")


@functools.lru_cache(maxsize=2)
def params(cfg):
    return T.init_params(jax.random.PRNGKey(0), cfg)


def serve(cfg, lengths, max_len, **kw):
    """Serve one request per (prompt, output) length, one fetch per
    tick; returns the engine, its requests and each prefill chunk's
    last-position logits."""
    eng = ContinuousEngine(cfg, params(cfg), 2, max_len, chunk=8,
                           sync=True, **kw)
    reqs = [Request(i, jax.random.randint(jax.random.PRNGKey(40 + i),
                                          (1, p), 2, cfg.vocab),
                    max_new=o) for i, (p, o) in enumerate(lengths)]
    for r in reqs:
        eng.submit(r)
    logits = []
    while eng.tick():
        if eng.prefill_logits is not None:
            logits.append(np.asarray(eng.prefill_logits, np.float32))
            eng.prefill_logits = None
    return eng, reqs, logits


LENGTHS = [(8, 30), (16, 40), (24, 12)]


def test_attention_free_engine_compiles_one_program_per_tick_kind():
    assert not attends(FALCON) and attends(GRANITE)
    one, reqs, logits = serve(FALCON, LENGTHS, 96, max_new=40)
    assert one.page == 96
    assert [getattr(one, t)._cache_size() for t in TICKS] == [1, 1, 1]
    # the same requests with a KV bound per 8-row page: a decode program
    # for each page the lengths reach, and the same tokens and logits
    paged, paged_reqs, paged_logits = serve(FALCON, LENGTHS, 96, page=8,
                                            max_new=40)
    assert paged._decode._cache_size() > 1
    assert [r.generated for r in reqs] == [r.generated for r in paged_reqs]
    assert len(logits) == len(paged_logits) > 0
    for a, b in zip(logits, paged_logits):
        assert np.array_equal(a, b)


def test_ssm_decode_logits_do_not_depend_on_the_kv_bound():
    p = params(FALCON)
    cache = T.init_cache(FALCON, 2, 64)
    tok = jnp.array([[5], [9]], jnp.int32)
    lens = jnp.array([3, 7], jnp.int32)
    act = jnp.array([True, True])
    step = jax.jit(T.serve_decode_step, static_argnums=(1, 6))
    a, ca = step(p, FALCON, tok, cache, lens, act, 8)
    b, cb = step(p, FALCON, tok, cache, lens, act, 64)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    for x, y in zip(jax.tree.leaves(ca), jax.tree.leaves(cb)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_granite_engine_still_buckets_by_the_attention_plan_page():
    max_len = 160
    plan = akg.plan_attention(8, max_len, GRANITE.hd)
    page = max(min(plan.tile.get("kk", 128), max_len), 8)
    eng, reqs, _ = serve(GRANITE, [(120, 24)], max_len, max_new=24)
    assert eng.page == page < max_len
    assert all(r.done for r in reqs)
    # the decode lengths cross a page boundary: one program per bound
    assert eng._decode._cache_size() == 2


def _decode_inline(p, cfg, x, conv_state, ssm_state):
    """One-token Mamba decode written out whole, with the B/C/Δ norm
    where the config sets it: what ``mamba_decode`` computes."""
    di, st, dtr = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    xs, z = jnp.split(x @ p["in_proj"], [di], axis=-1)
    hist = jnp.concatenate([conv_state.astype(jnp.float32),
                            xs.astype(jnp.float32)], axis=1)
    conv = jnp.einsum("bcd,cd->bd", hist, p["conv_w"].astype(jnp.float32))
    xs1 = jax.nn.silu(conv + p["conv_b"]).astype(x.dtype)
    proj = (xs1 @ p["x_proj"]).astype(jnp.float32)
    dt_r, Bm, Cm = jnp.split(proj, [dtr, dtr + st], axis=-1)
    if cfg.bcdt_rms_eps:
        def rms(v):
            return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                     + cfg.bcdt_rms_eps)
        dt_r, Bm, Cm = rms(dt_r), rms(Bm), rms(Cm)
    dt = jax.nn.softplus(dt_r @ p["dt_proj"].astype(jnp.float32)
                         + p["dt_bias"])
    a_bar = jnp.exp(dt[..., None] * -jnp.exp(p["a_log"]))
    b_bar = (dt[..., None] * Bm[:, None, :]) \
        * xs1.astype(jnp.float32)[..., None]
    h = ssm_state * a_bar + b_bar
    y = jnp.einsum("bdn,bn->bd", h, Cm) \
        + xs1.astype(jnp.float32) * p["d_skip"]
    y = (y.astype(x.dtype) * jax.nn.silu(z[:, 0]))[:, None, :]
    return y @ p["out_proj"], hist[:, 1:].astype(conv_state.dtype), h


@pytest.mark.parametrize("eps", [1e-6, 0.0])
def test_mamba_decode_shares_the_discretisation(eps):
    cfg = FALCON.scaled(bcdt_rms_eps=eps)
    assert FALCON.bcdt_rms_eps == 1e-6 and FALCON.norm_eps == 1e-5
    p = jax.tree.map(lambda v: v[0],
                     params(FALCON)["decoder"]["slots"][0]["mixer"])
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(k[0], (2, 1, cfg.d_model)).astype(jnp.bfloat16)
    conv = jax.random.normal(
        k[1], (2, cfg.conv_width - 1, cfg.d_inner)).astype(jnp.bfloat16)
    h0 = jax.random.normal(k[2], (2, cfg.d_inner, cfg.ssm_state))
    got = jax.jit(SSM.mamba_decode, static_argnums=1)(p, cfg, x, conv, h0)
    want = jax.jit(_decode_inline, static_argnums=1)(p, cfg, x, conv, h0)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    # the norm moves the output: it is applied, not only declared
    if eps:
        plain = jax.jit(_decode_inline, static_argnums=1)(
            p, cfg.scaled(bcdt_rms_eps=0.0), x, conv, h0)
        assert not np.allclose(np.asarray(plain[0], np.float32),
                               np.asarray(got[0], np.float32), atol=1e-2)


def _scan_carry_dtypes(fn, *args):
    """Dtypes of the carries of every ``scan`` in ``fn``'s program."""
    out = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "scan":
                n = e.params["num_consts"]
                out.extend(v.aval.dtype for v in
                           e.invars[n:n + e.params["num_carry"]])
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


@pytest.mark.parametrize("cfg,stream", [(FALCON, jnp.float32),
                                        (GRANITE, jnp.bfloat16)])
def test_residual_stream_dtype_between_layers(cfg, stream):
    """Falcon-Mamba carries the residual stream from layer to layer in
    float32 (``residual_in_fp32``); granite keeps it in bfloat16.  The
    carry of the serving layer scans is that stream."""
    assert cfg.residual_f32 == (stream == jnp.float32)
    p = params(cfg)
    cache = T.init_cache(cfg, 2, 32)
    tok = jnp.array([[5], [9]], jnp.int32)
    lens = jnp.array([3, 7], jnp.int32)
    act = jnp.array([True, True])
    dec = _scan_carry_dtypes(
        lambda p, c: T.serve_decode_step(p, cfg, tok, c, lens, act, 32),
        p, cache)
    view = T.cache_slot_view(cache, 0)
    chunk = _scan_carry_dtypes(
        lambda p, c: T.chunk_step(p, cfg, jnp.ones((1, 8), jnp.int32), c,
                                  jnp.int32(0), 32), p, view)
    assert dec == chunk == [jnp.dtype(stream)]
    logits, _ = jax.jit(T.serve_decode_step, static_argnums=(1, 6))(
        p, cfg, tok, cache, lens, act, 32)
    assert logits.dtype == jnp.dtype(cfg.dtype)


@pytest.mark.parametrize("rows", [32, 44])
def test_mamba_chunk_fused_and_jnp_routes_agree(rows):
    """The fused route (Δ, A, B, C into ``scan_gate``, discretised in
    the kernel) and the jnp route (``discretise`` then the associative
    scan) give one chunk's output, conv tail and carried state, from a
    carried state, with Falcon-Mamba's B/C/Δ norm on."""
    from repro.model import pallas_mode
    assert FALCON.bcdt_rms_eps
    p = jax.tree.map(lambda v: v[0],
                     params(FALCON)["decoder"]["slots"][0]["mixer"])
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (2, rows, FALCON.d_model)).astype(jnp.bfloat16)
    conv = jax.random.normal(
        k[1], (2, FALCON.conv_width - 1, FALCON.d_inner)).astype(jnp.bfloat16)
    h0 = jax.random.normal(k[2], (2, FALCON.d_inner, FALCON.ssm_state))

    def route(enabled):
        with pallas_mode.pallas_mode(enabled=enabled, min_scan_seq=32):
            f = functools.partial(SSM.mamba_chunk, p, FALCON)
            return (str(jax.make_jaxpr(f)(x, conv, h0)),
                    jax.jit(f)(x, conv, h0))

    (fused_text, fused), (plain_text, plain) = route(True), route(False)
    assert "pallas_call" in fused_text and "pallas_call" not in plain_text
    assert np.array_equal(np.asarray(fused[1], np.float32),
                          np.asarray(plain[1], np.float32))
    np.testing.assert_allclose(np.asarray(fused[2]), np.asarray(plain[2]),
                               rtol=1e-4, atol=1e-4)
    # within the bf16 rounding of the output (a zero h0 moves it by ~1.5)
    out_f, out_p = (np.asarray(o, np.float32) for o in (fused[0], plain[0]))
    np.testing.assert_allclose(out_f, out_p, rtol=0.02,
                               atol=0.02 * np.abs(out_p).max())
