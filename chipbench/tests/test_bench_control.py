"""The control: the reference in the program's place one precision step
below the configuration's (float8 e4m3 for bfloat16), judged by the same
checks on the same served positions, comes out not correct where the
program comes out correct, at a small width."""
import pytest

import bench_tiny


@pytest.mark.parametrize("name", sorted(bench_tiny.CELLS))
def test_float8_control_is_not_correct(name):
    from harness import cell as C
    from harness import check as CK
    c = bench_tiny.cell(name)
    seeds = [2 ** 31 + 11, 2 ** 31 + 12, 2 ** 31 + 13]
    sess = C.Session(c, seeds[0], check_device=False)
    length, width = sess.mix["engine"]["max_len"], sess.out_max
    limit = c["conf"]["correct"]["max_logit_gap"]
    for seed in seeds:
        if seed != sess.seed:
            sess.use_seed(seed)
        win = sess.window(2.0, False)
        finite = sess.engine.logits_finite()
        done = C.finished(win)
        picked = CK.sample(done, len(done), seed)
        ref = sess.ref.Reference(sess.conf, seed)
        prog = CK.gaps(ref, picked, length, width)
        ctl = CK.gaps(ref, picked, length, width, precision="fp8",
                      pick_own=True)
        assert prog.size == ctl.size > 0
        program = C.checks_of(sess, win, prog, limit, finite)
        control = C.checks_of(sess, win, ctl, limit, finite)
        assert C.passes(program), (seed, program)
        assert not C.passes(control), (seed, control)
