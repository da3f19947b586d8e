"""Serving-engine bench: continuous batching + Pallas fast path on the
smoke config (CPU).

The engine runs a greedy workload (B prompts of one length, a fixed
token budget) with warmed jits, and the gate requires its generated
tokens to be **bit-identical** to each request's reference alone
(batch-1 ``prefill`` then a ``decode_step`` loop) — the engine's chunked
prefill, paged KV, fused decode dispatches, and Pallas kernels must not
change a single logit argmax.  Reported:

* ``tokens_per_s``           — median-of-REPS wall-clock throughput of
  the async run (a CPU timing: evidence of overhead, not of speed)
* ``p50/p99_inter_token_ms`` — from a ``sync=True`` run (per-tick host
  sync so each token has a timestamp)
* ``overlap_ratio``          — fraction of busy engine ticks that ran a
  prefill chunk and a decode dispatch together

Gated metrics (host-portable, see scripts/bench_compare.py):
``tokens_identical`` and ``paged_memory_ratio`` — the roofline
memory-term ratio of the full-cache ``decode_step`` vs the paged decode
step, derived from compiled HLO ``cost_analysis()`` through
:mod:`repro.launch.roofline` (structural: counts bytes the compiled
step touches, not wall clock).

Writes ``BENCH_serve.json`` next to this file.

Usage: PYTHONPATH=src python -m benchmarks.bench_serve
Env:   POLYTOPS_SERVE_BATCH    slots            (default 4)
       POLYTOPS_SERVE_PLEN     prompt length    (default 32)
       POLYTOPS_SERVE_GEN      tokens/request   (default 32)
       POLYTOPS_SERVE_MAXLEN   cache rows       (default 256)
       POLYTOPS_SERVE_CHUNK    prefill chunk    (default 16)
       POLYTOPS_SERVE_REPS     timed reps       (default 5)
"""
from __future__ import annotations

import json
import os
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.registry import ShapeConfig, get_arch
from repro.launch.roofline import (collective_bytes_from_hlo,
                                   roofline_terms)
from repro.launch.serve import ContinuousEngine, Request
from repro.model import pallas_mode
from repro.model import transformer as T

HERE = Path(__file__).resolve().parent
OUT = HERE / "BENCH_serve.json"

ARCH = os.environ.get("POLYTOPS_SERVE_ARCH", "granite_3_2b")
B = int(os.environ.get("POLYTOPS_SERVE_BATCH", "4"))
PLEN = int(os.environ.get("POLYTOPS_SERVE_PLEN", "32"))
GEN = int(os.environ.get("POLYTOPS_SERVE_GEN", "32"))
MAXLEN = int(os.environ.get("POLYTOPS_SERVE_MAXLEN", "256"))
CHUNK = int(os.environ.get("POLYTOPS_SERVE_CHUNK", "16"))
REPS = int(os.environ.get("POLYTOPS_SERVE_REPS", "5"))


def _prompts(cfg, key):
    return [jax.random.randint(jax.random.fold_in(key, i), (1, PLEN), 2,
                               cfg.vocab) for i in range(B)]


def _reference(cfg, params, prompts):
    """Each prompt alone at batch 1: ``prefill`` into the first rows of
    a fresh cache, then GEN-1 greedy ``decode_step``s."""
    prefill = jax.jit(lambda p, t: T.prefill(p, cfg, t))
    step = jax.jit(lambda p, t, c, n: T.decode_step(p, cfg, t, c, n))
    out = []
    for pr in prompts:
        logits, pre = prefill(params, pr)
        cache = jax.tree.map(
            lambda c, v: jax.lax.dynamic_update_slice(
                c, v.astype(c.dtype), (0,) * c.ndim),
            T.init_cache(cfg, 1, MAXLEN), pre)
        toks = [int(jnp.argmax(logits[0]))]
        for n in range(PLEN, PLEN + GEN - 1):
            logits, cache = step(params, jnp.asarray([[toks[-1]]]), cache,
                                 jnp.int32(n))
            toks.append(int(jnp.argmax(logits[0])))
        out.append(toks)
    return out


def _run_continuous(eng, prompts):
    reqs = [Request(i, p) for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return reqs


def _timed(run, eng, prompts):
    times = []
    for _ in range(REPS):
        eng.reset()
        t0 = time.time()
        reqs = run(eng, prompts)
        times.append(time.time() - t0)
    ntok = sum(len(r.generated) for r in reqs)
    med = statistics.median(times)
    return {"tokens": ntok, "wall_s_median": round(med, 5),
            "wall_s_best": round(min(times), 5),
            "tokens_per_s": round(ntok / med, 1)}, reqs


def _latency(eng, prompts):
    eng.reset()
    reqs = _run_continuous(eng, prompts)
    gaps = []
    for r in reqs:
        ts = r.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    gaps.sort()
    if not gaps:
        return {"p50_ms": 0.0, "p99_ms": 0.0}, reqs
    p50 = gaps[len(gaps) // 2]
    p99 = gaps[min(int(len(gaps) * 0.99), len(gaps) - 1)]
    return {"p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
            "gaps": len(gaps)}, reqs


def _decode_roofline(cfg, lengths):
    """Roofline terms for one compiled decode dispatch: the full-cache
    ``decode_step`` vs the paged ``serve_decode_step``."""
    params = jax.eval_shape(lambda k: T.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: T.init_cache(cfg, B, MAXLEN))
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    shape = ShapeConfig("serve_decode", MAXLEN, B, "decode")

    def stats(fn, *args, **kw):
        compiled = jax.jit(fn, **kw).lower(*args).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        coll = collective_bytes_from_hlo(compiled.as_text())
        return roofline_terms(cfg, shape, cost, coll, 1)

    full = stats(lambda p, t, c: T.decode_step(p, cfg, t, c, MAXLEN - 1),
                 params, toks, cache)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32)
    act = jax.ShapeDtypeStruct((B,), jnp.bool_)
    kv = lengths  # page-aligned bucket actually used mid-run
    paged = stats(lambda p, t, c, l, a:
                  T.serve_decode_step(p, cfg, t, c, l, a, kv),
                  params, toks, cache, lens, act)
    return {"full": full, "paged": paged, "paged_kv_rows": kv,
            "full_kv_rows": MAXLEN}


def run(out=sys.stdout):
    cfg = get_arch(ARCH).smoke()
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    prompts = _prompts(cfg, key)

    ref_tokens = _reference(cfg, params, prompts)

    cont = ContinuousEngine(cfg, params, B, MAXLEN, chunk=CHUNK,
                            use_pallas=True, max_new=GEN)
    cont_reqs = _run_continuous(cont, prompts)        # warm compile
    cont_tokens = [r.generated for r in cont_reqs]
    cont_stats, last = _timed(_run_continuous, cont, prompts)
    overlap = cont.overlap_ratio()
    identical = (ref_tokens == cont_tokens
                 and cont_tokens == [r.generated for r in last])

    sync_eng = ContinuousEngine(cfg, params, B, MAXLEN, chunk=CHUNK,
                                use_pallas=True, max_new=GEN, sync=True)
    _run_continuous(sync_eng, prompts)                # warm compile
    lat, sync_reqs = _latency(sync_eng, prompts)
    identical = identical and cont_tokens == [r.generated
                                              for r in sync_reqs]
    pallas_mode.configure(enabled=False)

    roof = _decode_roofline(cfg, cont._bucket(PLEN + GEN))
    mem_ratio = roof["full"]["memory_s"] / max(roof["paged"]["memory_s"],
                                               1e-30)

    doc = {
        "arch": ARCH, "batch": B, "prompt_len": PLEN, "gen": GEN,
        "max_len": MAXLEN, "chunk": CHUNK, "reps": REPS,
        "page": cont.page,
        "continuous": cont_stats,
        "tokens_identical": int(identical),
        "overlap_ratio": round(overlap, 3),
        "inter_token": lat,
        "p99_over_p50_inter_token": round(
            lat["p99_ms"] / max(lat["p50_ms"], 1e-9), 3),
        "paged_memory_ratio": round(mem_ratio, 3),
        "roofline_decode": roof,
    }
    OUT.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    print(f"serve bench: continuous {cont_stats['tokens_per_s']} tok/s, "
          f"identical={bool(identical)}, "
          f"overlap={overlap:.2f}, page={cont.page}, "
          f"p99/p50 inter-token={doc['p99_over_p50_inter_token']}, "
          f"paged memory ratio={mem_ratio:.2f}", file=out)
    print(f"wrote {OUT}", file=out)
    return doc


def main(argv=None) -> int:
    doc = run()
    ok = doc["tokens_identical"] == 1
    if not ok:
        print("bench_serve: FAIL — the engine's greedy tokens differ from "
              "the prefill + decode_step reference", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
