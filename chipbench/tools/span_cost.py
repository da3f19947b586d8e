#!/usr/bin/env python3
"""What one host span costs: enter and exit of a
``jax.profiler.TraceAnnotation``, with the profiler off and recording,
ns per span, median of five rounds.

    python3 chipbench/tools/span_cost.py [--n 200000]

Prints one JSON line.  The recorded trace goes to the benchmark's
trace directory (``.bench_trace``), emptied first.
"""
import argparse
import json
import statistics
import time

import common  # noqa: F401  (paths and environment)


def per_span_ns(n: int) -> float:
    import jax
    t = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("engine.tick"):
            pass
    return (time.perf_counter() - t) / n * 1e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200000)
    args = ap.parse_args()

    import jax
    from harness import cell as C
    from harness import trace as TRC
    dev = jax.devices()[0]
    off = [per_span_ns(args.n) for _ in range(5)]
    jax.profiler.start_trace(TRC.fresh_dir(C.TRACE_DIR))
    on = [per_span_ns(args.n) for _ in range(5)]
    jax.profiler.stop_trace()
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "spans": args.n, "off_ns": statistics.median(off),
                      "recording_ns": statistics.median(on),
                      "off_rounds": off, "recording_rounds": on}))


if __name__ == "__main__":
    main()
