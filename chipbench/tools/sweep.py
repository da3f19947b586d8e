#!/usr/bin/env python3
"""Offer an open-loop cell's mix at several rates, one window each, in
one process, to find the highest rate the engine sustains (the knee).

    python3 chipbench/tools/sweep.py --workload <cell> --seed <n> \\
        --seconds 20 --rates 2,3,4,5 [--describe]

Per rate it prints one JSON line: requests due, finished by the close,
the backlog (sent, not finished) at the close and its mean over the last
third of the window against the first, TTFT and inter-token tails, and
how long the drain took.
"""
import argparse
import json
import time

import common


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import numpy as np
    import jax
    from harness import cell as C

    c = common.cell(args.workload)
    sess = C.Session(c, args.seed)
    C.log(f"sweep set-up {time.time() - common.T_START:.1f} s")
    for rate in (float(r) for r in args.rates.split(",")):
        sess.mix["arrivals"]["rate"] = rate
        sess.engine.reset()
        win = sess.window(args.seconds, False, drain_s=30.0)
        served = win["served"]
        t_open, t_close = win["t_open"], win["t_close"]
        # backlog from the requests' own times
        times = np.linspace(t_open, t_close, 60)
        firsts = [s.req.t_first or np.inf for s in served]
        back = [sum(s.due <= t and f > t for s, f in zip(served, firsts))
                for t in times]
        ttft = [(f - s.due) * 1e3 for s, f in zip(served, firsts)
                if np.isfinite(f)]
        gaps = []
        for s in served:
            tt = [t for t in s.req.token_times if t <= t_close]
            gaps += [(b - a) * 1e3 for a, b in zip(tt, tt[1:])]
        drain = max((s.req.token_times[-1] for s in served
                     if s.req.token_times), default=t_close) - t_close
        row = {"rate": rate, "due": len(served),
               "unfinished_at_close": sum(
                   not s.req.token_times or s.req.token_times[-1] > t_close
                   for s in served),
               "waiting_first_third": float(np.mean(back[:20])),
               "waiting_last_third": float(np.mean(back[40:])),
               "ttft_p50_ms": float(np.percentile(ttft, 50)),
               "ttft_p75_ms": float(np.percentile(ttft, 75)),
               "ttft_p90_ms": float(np.percentile(ttft, 90)),
               "itl_p50_ms": float(np.percentile(gaps, 50)),
               "itl_p95_ms": float(np.percentile(gaps, 95)),
               "drain_s": drain, "compiles": win["compiles"],
               "ticks": len(win["ticks"])}
        print(json.dumps(row), flush=True)
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use")}))


if __name__ == "__main__":
    main()
