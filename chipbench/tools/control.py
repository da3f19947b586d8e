#!/usr/bin/env python3
"""Readings that set a cell's correctness limit: for each seed, one
short window of the cell's own traffic through the program, then the
widest logit gap of the served tokens against the float32 reference
(the program's reading) and of the tokens a float8 reference puts first
at the same positions (the control's reading), on the same sample.
Both go through the run's own checks (``cell.checks_of``, ``passes``),
so each row says whether the program and the control come out correct.

    python3 chipbench/tools/control.py --workload <cell> \\
        --seeds 11,12,13 --seconds 20

One process: the engine is warmed once and each seed swaps in its own
weights.  Prints one JSON line per seed and appends it to
``chiprun_out/control_<cell>.jsonl``.
"""
import argparse
import json
import os
import time

import common


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    args = ap.parse_args()

    from harness import cell as C, check as CK

    c = common.cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    sess = C.Session(c, seeds[0])
    C.log(f"control set-up {time.time() - common.T_START:.1f} s")
    os.makedirs(common.OUT, exist_ok=True)
    path = os.path.join(common.OUT, f"control_{args.workload}.jsonl")
    for seed in seeds:
        if seed != sess.seed:
            sess.use_seed(seed)
        win = sess.window(args.seconds, False, sess.plan(args.seconds))
        picked = CK.sample(C.finished(win), sess.mix["check"]["requests"],
                           seed)
        ref = sess.ref.Reference(sess.conf, seed)
        t = time.time()
        prog = CK.gaps(ref, picked, sess.mix["engine"]["max_len"],
                       sess.out_max)
        t_ref = time.time() - t
        finite = sess.engine.logits_finite()
        limit = sess.conf["correct"]["max_logit_gap"]
        checks = C.checks_of(sess, win, prog, limit, finite)
        row = {"seed": seed, "program_correct": C.passes(checks),
               "program_max_gap": checks["max_logit_gap"]["value"],
               "tokens": int(prog.size), "requests": len(picked),
               "due": len(win["served"]),
               "compiles_in_window": win["compiles"],
               "reference_s": t_ref}
        ctl = CK.gaps(ref, picked, sess.mix["engine"]["max_len"],
                      sess.out_max, precision="fp8", pick_own=True)
        cc = C.checks_of(sess, win, ctl, limit, finite)
        row["control_correct"] = C.passes(cc)
        row["control_max_gap"] = cc["max_logit_gap"]["value"]
        row["control_share_nonzero"] = float((ctl > 0).mean())
        print(json.dumps(row), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main()
