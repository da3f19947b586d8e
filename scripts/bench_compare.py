"""Bench-regression gate: fresh BENCH_*.json vs committed baselines.

Compares the metrics below against ``benchmarks/baselines/`` with
per-metric tolerances and fails (exit 1) on regression.  Only
**host-portable** metrics are gated — ratios of same-run/same-machine
measurements (speedups, latency ratios) and structural counts
(computations, errors) — never absolute milliseconds, which would gate
the CI runner's clock speed instead of the code.

Direction semantics:

* ``higher`` — regression when ``fresh < baseline * (1 - tol)``
* ``lower``  — regression when ``fresh > baseline * (1 + tol)``
  (with a zero baseline, any positive fresh value regresses)

A fresh file that was not produced in this run skips its rows (the CI
matrix runs different bench gates in different jobs and each job
compares whatever it produced); a metric missing a baseline passes with
a note — commit a new baseline to start gating it.  If *nothing* fresh
matched, the gate fails: a comparison over zero metrics is not a gate.

Writes a markdown delta table (for the CI artifact) and prints it.

Usage: python scripts/bench_compare.py \
           [--fresh-dir benchmarks] \
           [--baseline-dir benchmarks/baselines] \
           [--out artifacts/bench_delta.md]

Refreshing baselines intentionally (after a real improvement or an
accepted trade-off):  copy the fresh file over the baseline, e.g.
``cp benchmarks/BENCH_loadgen.json benchmarks/baselines/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, List, Optional, Tuple

#: (file, dotted metric path, direction, relative tolerance)
SPEC: List[Tuple[str, str, str, float]] = [
    # scheduler smoke: decomposed pipeline vs the seed path, same run
    ("BENCH_scheduler_fast.json",
     "geomean_speedup_decomposed_vs_seed", "higher", 0.20),
    # daemon bench: coalescing is structural (N identical concurrent
    # requests -> exactly 1 computation), warm-hit ratio is same-host
    ("BENCH_schedd.json", "coalescing.computed", "lower", 0.0),
    ("BENCH_schedd.json", "warm_latency.ratio_p50", "lower", 0.75),
    ("BENCH_schedd.json", "frame_hit_rate", "higher", 0.25),
    # load generator: dispatch-concurrency speedup and tail flatness
    ("BENCH_loadgen.json", "speedup_distinct_4v1", "higher", 0.25),
    ("BENCH_loadgen.json", "p99_over_p50_at_max_workers", "lower", 0.50),
    ("BENCH_loadgen.json", "errors_total", "lower", 0.0),
    ("BENCH_loadgen.json", "shared_computed_at_max_workers", "lower", 0.0),
    # TCP transport: authenticated localhost TCP vs Unix, same daemon,
    # same run — the ratio isolates handshake/MAC cost from host speed
    ("BENCH_loadgen_tcp.json", "tcp_over_unix_distinct", "higher", 0.15),
    ("BENCH_loadgen_tcp.json", "errors_total", "lower", 0.0),
    ("BENCH_loadgen_tcp.json", "shared_computed_tcp", "lower", 0.0),
    # serving engine: the greedy-token identity bit against the
    # prefill + decode_step reference; paged_memory_ratio is a
    # structural byte count (full KV bytes / paged KV bytes)
    ("BENCH_serve.json", "tokens_identical", "higher", 0.0),
    ("BENCH_serve.json", "paged_memory_ratio", "higher", 0.05),
]


def dig(obj: Any, path: str) -> Optional[float]:
    for part in path.split("."):
        if not isinstance(obj, dict) or part not in obj:
            return None
        obj = obj[part]
    return float(obj) if isinstance(obj, (int, float)) else None


def load(path: str) -> Optional[dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def compare(fresh_dir: str, baseline_dir: str):
    rows = []          # (file, metric, baseline, fresh, delta_pct, status)
    regressions = []
    compared = 0
    fresh_cache: dict = {}
    base_cache: dict = {}
    for fname, path, direction, tol in SPEC:
        if fname not in fresh_cache:
            fresh_cache[fname] = load(os.path.join(fresh_dir, fname))
        if fname not in base_cache:
            base_cache[fname] = load(os.path.join(baseline_dir, fname))
        fresh_doc, base_doc = fresh_cache[fname], base_cache[fname]
        if fresh_doc is None:
            rows.append((fname, path, None, None, None,
                         "skipped — not produced in this run"))
            continue
        fresh = dig(fresh_doc, path)
        base = dig(base_doc, path) if base_doc is not None else None
        if fresh is None:
            regressions.append(f"{fname}:{path} missing from fresh run")
            rows.append((fname, path, base, None, None,
                         "FAIL — metric missing"))
            continue
        if base is None:
            rows.append((fname, path, None, fresh, None,
                         "no baseline — commit one to gate"))
            continue
        compared += 1
        if direction == "higher":
            bound = base * (1.0 - tol)
            bad = fresh < bound
        else:
            bound = base * (1.0 + tol)
            bad = fresh > bound
        delta_pct = (round((fresh - base) / base * 100.0, 1)
                     if base else None)
        if bad:
            arrow = "<" if direction == "higher" else ">"
            regressions.append(
                f"{fname}:{path} = {fresh:g} {arrow} allowed {bound:g} "
                f"(baseline {base:g}, tol {tol:.0%}, {direction} is better)")
            status = f"FAIL — past {bound:g}"
        else:
            status = "ok"
        rows.append((fname, path, base, fresh, delta_pct, status))
    return rows, regressions, compared


def markdown(rows) -> str:
    out = ["# Bench delta vs committed baselines", "",
           "| file | metric | baseline | fresh | delta | status |",
           "|---|---|---:|---:|---:|---|"]
    for fname, path, base, fresh, delta, status in rows:
        out.append("| {} | `{}` | {} | {} | {} | {} |".format(
            fname, path,
            "—" if base is None else f"{base:g}",
            "—" if fresh is None else f"{fresh:g}",
            "—" if delta is None else f"{delta:+.1f}%",
            status))
    out.append("")
    return "\n".join(out)


def main(argv=None) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fresh-dir", default=os.path.join(root, "benchmarks"))
    ap.add_argument("--baseline-dir",
                    default=os.path.join(root, "benchmarks", "baselines"))
    ap.add_argument("--out",
                    default=os.path.join(root, "artifacts",
                                         "bench_delta.md"))
    args = ap.parse_args(argv)

    rows, regressions, compared = compare(args.fresh_dir, args.baseline_dir)
    table = markdown(rows)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        f.write(table)
    print(table)
    if compared == 0:
        print("bench_compare: FAIL — no fresh metric matched a baseline "
              "(ran without any bench output?)", file=sys.stderr)
        return 1
    if regressions:
        print(f"bench_compare: {len(regressions)} regression(s):",
              file=sys.stderr)
        for r in regressions:
            print(f"  - {r}", file=sys.stderr)
        return 1
    print(f"bench_compare: OK — {compared} metric(s) within tolerance "
          f"({args.out})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
