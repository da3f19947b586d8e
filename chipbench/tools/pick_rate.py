#!/usr/bin/env python3
"""Set an open-loop mix's rate to four fifths of the knee a sweep found.

    python3 chipbench/tools/pick_rate.py <sweep output> <mix file>

The knee is the highest swept rate at which the backlog did not grow:
the requests waiting for their first token, averaged over the window's
last third, exceed those over its first third by at most one, and the
drain after the close took under five seconds.
"""
import json
import sys


def main():
    rows = [json.loads(ln) for ln in open(sys.argv[1])
            if ln.startswith('{"rate"')]
    ok = [r["rate"] for r in rows
          if r["waiting_last_third"] - r["waiting_first_third"] <= 1.0
          and r["drain_s"] < 5.0]
    if not ok:
        sys.exit("no swept rate kept the backlog flat")
    knee = max(ok)
    with open(sys.argv[2]) as f:
        mix = json.load(f)
    mix["arrivals"]["rate"] = round(0.8 * knee, 2)
    text = json.dumps(mix, indent=2)
    with open(sys.argv[2], "w") as f:
        f.write(text + "\n")
    print(json.dumps({"knee": knee, "rate": mix["arrivals"]["rate"]}))


if __name__ == "__main__":
    main()
