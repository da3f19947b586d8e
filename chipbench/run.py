#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (a ``workloads`` entry of ``BENCHMARK.json``) names a model
configuration and a traffic mix; their files are found by name (see
``harness/spec.py``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number compared with its limit.  Those numbers are also the last lines
of standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# kernel plans are made in this process, never by a scheduling daemon
for _var in ("POLYTOPS_SCHEDD_SOCK", "POLYTOPS_SCHEDD_ADDR"):
    os.environ.pop(_var, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cell as C
    from harness import spec

    c = spec.resolve(spec.benchmark(), args.workload)
    try:
        result = C.run(c, args.seed, args.seconds, bool(args.trace), T_START)
    except C.NoDevice as e:
        C.log(f"no result: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
