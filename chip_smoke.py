#!/usr/bin/env python3
"""Smoke test of the serving path on one TPU chip.

Serves granite_3_2b at its published widths (40 layers, d_model 2048,
random weights from ``--seed``) through ``ContinuousEngine`` — the path
``python -m repro.launch.serve --arch granite_3_2b --pallas`` takes — with
the Pallas kernels compiled by Mosaic:

    python3 chip_smoke.py                  # on a machine with one TPU
    JAX_PLATFORMS=cpu python3 chip_smoke.py --smoke
                                           # control-flow rehearsal at
                                           # smoke width; never reports ok

Phases, one line each: device, plans, kernels, parity, serve.  Any
failure exits non-zero.  The last line of a passing run is one JSON
object: {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
One process touches JAX; no child process is started, and kernel plans
are made in-process (any schedd address in the environment is ignored).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
for _var in ("POLYTOPS_SCHEDD_SOCK", "POLYTOPS_SCHEDD_ADDR"):
    os.environ.pop(_var, None)

ARCH = "granite_3_2b"
CHUNK = 256          # prefill rows per tick: clears min_attn_q and min_matmul_rows
MAX_LEN = 2048       # KV rows per slot
SLOTS = 4
MAX_NEW = 32
PARITY_LEN = 512
#: serve-phase prompt lengths (8 requests); multiples of CHUNK keep the
#: number of KV buckets, and so of compiled tick programs, small
PROMPT_LENS = (256, 256, 256, 1024, 256, 256, 256, 1024)
#: parity: max|Δ logit| ≤ REL_TOL · max|logit| of the jnp engine, and the
#: Pallas engine's top token is the jnp engine's, or within that bound
#: of the jnp engine's top logit
REL_TOL = 2e-2


def fail(msg: str):
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


class CompileCounter:
    """XLA executables built (compiled, or read from the persistent
    cache) and the seconds spent on them, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == self.EVENT:
            self.count += 1
            self.secs += secs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse at smoke width (any backend); "
                         "the run never reports ok")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import (ContinuousEngine, Request, init_params,
                                    warm_kernel_plans)

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()

    # -- device ----------------------------------------------------------
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    on_tpu = dev["platform"] == "tpu"
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']} "
          f"(compile cache {cache_dir})", flush=True)
    if not on_tpu and not args.smoke:
        fail(f"no TPU: JAX's default device is {dev['platform']}")

    cfg = get_arch(ARCH)
    if args.smoke:
        cfg = cfg.smoke()

    # -- plans -----------------------------------------------------------
    degraded = warm_kernel_plans(cfg, MAX_LEN, CHUNK)
    if degraded:
        fail(f"{degraded} kernel plans degraded")
    print(f"plans: {cfg.name} at max_len {MAX_LEN}, chunk {CHUNK}: "
          f"none degraded", flush=True)

    # -- kernels ---------------------------------------------------------
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, args.seed))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"params: {n_params} ({cfg.n_layers} layers, d_model "
          f"{cfg.d_model}) made on the device in "
          f"{time.perf_counter() - t0:.3f} s", flush=True)
    # pallas_mode is read at trace time; each engine owns its jits, so
    # the two engines' programs never share a trace
    ref_eng = ContinuousEngine(cfg, params, 1, MAX_LEN, chunk=CHUNK,
                               max_new=MAX_NEW)
    eng = ContinuousEngine(cfg, params, SLOTS, MAX_LEN, chunk=CHUNK,
                           use_pallas=True, max_new=MAX_NEW)
    hlo = eng.lower_chunk(CHUNK, CHUNK).compile().as_text()
    kernels = sorted(set(re.findall(
        r'op_name="[^"]*/(\w+)/pallas_call"',
        "\n".join(ln for ln in hlo.splitlines() if "tpu_custom_call" in ln))))
    print(f"kernels: Pallas chunk tick holds tpu_custom_call: "
          f"{'tpu_custom_call' in hlo}; kernels {kernels}", flush=True)
    if not on_tpu:
        print("kernels: not a TPU, Mosaic kernels not checked", flush=True)
    elif "tpu_custom_call" not in hlo:
        fail("the Pallas chunk tick compiled without a Mosaic kernel")

    # -- parity ----------------------------------------------------------
    key = jax.random.PRNGKey(args.seed + 1)
    prompt = jax.random.randint(key, (1, PARITY_LEN), 2, cfg.vocab)
    logits = []
    for e in (ref_eng, eng):
        e.reset()
        e.submit(Request(0, prompt, max_new=1))
        e.run()
        logits.append(np.asarray(e.prefill_logits, np.float32))
    ref, got = logits
    scale = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(got - ref)))
    tol = REL_TOL * scale
    top_ref, top_got = int(np.argmax(ref)), int(np.argmax(got))
    top_ok = top_ref == top_got or ref[top_got] >= ref[top_ref] - tol
    print(f"parity: {PARITY_LEN}-token prefill, last-position logits, "
          f"Pallas vs jnp: max|d|={err!r} tol={tol!r} "
          f"(= {REL_TOL} x max|logit| {scale!r}); argmax jnp {top_ref} "
          f"Pallas {top_got}", flush=True)
    if not (np.all(np.isfinite(ref)) and np.all(np.isfinite(got))):
        fail("non-finite prefill logits")
    if err > tol or not top_ok:
        fail("Pallas and jnp logits disagree beyond the tolerance")
    del ref_eng

    # -- serve -----------------------------------------------------------
    def serve_once():
        eng.reset()
        reqs = [Request(i, jax.random.randint(
                    jax.random.fold_in(key, 100 + i), (1, n), 2, cfg.vocab),
                    max_new=MAX_NEW)
                for i, n in enumerate(PROMPT_LENS)]
        t = time.perf_counter()
        for r in reqs:
            eng.submit(r)
        ticks = eng.run()
        jax.block_until_ready(eng.cache)
        return reqs, ticks, time.perf_counter() - t

    c0, s0 = compiles.count, compiles.secs
    _, _, warm_s = serve_once()                       # compiles every tick
    c1, s1 = compiles.count, compiles.secs
    reqs, ticks, wall = serve_once()                  # timed: no compiles
    for r in reqs:
        if not r.done or len(r.generated) != MAX_NEW:
            fail(f"request {r.rid}: {len(r.generated)} of {MAX_NEW} tokens")
        if not all(0 <= t < cfg.vocab for t in r.generated):
            fail(f"request {r.rid}: token outside [0, {cfg.vocab})")
    if not eng.logits_finite():
        fail("a serving tick produced a non-finite logit")
    stats = devs[0].memory_stats() or {}
    print(f"serve: {len(reqs)} requests (prompts {list(PROMPT_LENS)}, "
          f"{MAX_NEW} new tokens each, {SLOTS} slots) all complete in "
          f"{ticks} ticks; host wall time to block_until_ready "
          f"{wall!r} s with {compiles.count - c1} compiles in the window; "
          f"set-up pass {warm_s!r} s with {c1 - c0} compiles "
          f"({s1 - s0!r} s); whole run {compiles.count} compiles "
          f"({compiles.secs!r} s); peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}", flush=True)

    if args.smoke or not on_tpu:
        print("rehearsal finished: no result is reported off the chip "
              "or at smoke width", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
