#!/usr/bin/env python3
"""Cut a slice of a recorded trace for the trace-reduction test, with the
values the test expects computed by a sweep over interval ends (counting
the operations and spans open at each instant), apart from the
reduction's own merging.

    python3 chipbench/tools/trace_slice.py <trace> <out.json> \\
        --ms 100 --source "<where it was recorded>" [--needles a,b,c]

The input is a profiler directory (``.bench_trace`` after a run with
``--trace 1``) or a normalised trace (``harness.trace.save_json``).  The
slice is the first ``--ms`` milliseconds of its ``bench.window`` span,
which becomes the slice's window.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import trace as TRC  # noqa: E402


def covered(intervals, lo, hi, inside=None):
    """Time in [lo, hi) where at least one interval is open (and, with
    ``inside``, at least one of those is open too), by a sweep."""
    ev = []
    for s, e in intervals:
        ev += [(max(s, lo), 0, 1), (min(e, hi), 0, -1)] if e > lo and s < hi else []
    for s, e in inside or []:
        ev += [(max(s, lo), 1, 1), (min(e, hi), 1, -1)] if e > lo and s < hi else []
    ev.sort()
    open_ = [0, 0]
    t_prev, out = lo, 0.0
    for t, kind, d in ev:
        if open_[0] > 0 and (inside is None or open_[1] > 0):
            out += t - t_prev
        open_[kind] += d
        t_prev = t
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("events")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=100.0)
    ap.add_argument("--source", required=True)
    ap.add_argument("--needles", default="")
    args = ap.parse_args()
    if os.path.isdir(args.events):
        tr = TRC.load(args.events)
    else:
        with open(args.events) as f:
            tr = TRC.Trace.from_json(json.load(f))
    lo, _ = tr.window()
    hi = lo + args.ms * 1e6
    ops = [o for o in tr.ops if o[2] > lo and o[1] < hi]
    spans = [s for s in tr.spans if s[0] != TRC.WINDOW and s[2] > lo and s[1] < hi]
    spans.append((TRC.WINDOW, lo, hi))
    ticks = [(s, e) for n, s, e in spans if n == "bench.tick"]
    busy = covered([(s, e) for _, s, e, _ in ops], lo, hi)
    needles = [n for n in args.needles.split(",") if n]
    expected = {
        "busy_s": busy * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "op_seconds": {k: covered([(s, e) for n, s, e, m in ops
                                   if k in n or k in m], lo, hi) * 1e-9
                       for k in needles},
        "tick_idle_s": (covered(ticks, lo, hi)
                        - covered([(s, e) for _, s, e, _ in ops], lo, hi,
                                  inside=ticks)) * 1e-9,
    }
    with open(args.out, "w") as f:
        json.dump({"source": args.source, "ops": ops, "spans": spans,
                   "expected": expected}, f)
    print(json.dumps({"ops": len(ops), "spans": len(spans), **expected}))


if __name__ == "__main__":
    main()
