"""The traffic generator: reproducible from the seed, lengths on their
grids and inside their clips, the same work for every seed."""
import os
from collections import Counter

import pytest

from harness import spec
from harness import traffic as TR

MIXES = os.path.join(spec.BENCH_DIR, "traffic")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# every mix the benchmark has, and a small one with bursts
PATHS = {"chat_poisson": os.path.join(MIXES, "chat_poisson.json"),
         "tiny_chat": os.path.join(DATA, "tiny_chat.json")}


def _mix(name):
    return spec.load_json(PATHS[name])


@pytest.mark.parametrize("name", sorted(PATHS))
def test_open_schedule_is_reproducible_and_on_grid(name):
    mix = _mix(name)
    a = TR.open_schedule(mix, 40.0, 2 ** 31 + 5)
    b = TR.open_schedule(mix, 40.0, 2 ** 31 + 5)
    assert [(p.due, p.prompt_len, p.out_len) for p in a] == \
        [(p.due, p.prompt_len, p.out_len) for p in b]
    c = TR.open_schedule(mix, 40.0, 2 ** 31 + 6)
    assert [p.due for p in a] != [p.due for p in c]
    pr, out = mix["prompt"], mix["output"]
    for p in a:
        assert pr["min"] <= p.prompt_len <= pr["max"]
        assert p.prompt_len % pr["grid"] == 0
        assert out["min"] <= p.out_len <= out["max"]
        assert 0 <= p.due < 40.0
    assert [p.due for p in a] == sorted(p.due for p in a)


@pytest.mark.parametrize("name", sorted(PATHS))
def test_every_seed_gets_the_same_work(name):
    mix = _mix(name)
    runs = [TR.open_schedule(mix, 40.0, s) for s in (1, 2, 3, 2 ** 33)]
    assert len({len(r) for r in runs}) == 1
    assert len({tuple(sorted(Counter(p.prompt_len for p in r).items()))
                for r in runs}) == 1
    assert len({tuple(sorted(Counter(p.out_len for p in r).items()))
                for r in runs}) == 1


def test_bursts_arrive_inside_their_spread():
    mix = _mix("tiny_chat")
    arr = mix["arrivals"]
    n = round(arr["rate"] * 40.0)
    n_burst = round(arr["burst_share"] * n)
    per = n_burst // int(40.0 // arr["burst_every_s"])
    for seed in range(5):
        plan = TR.open_schedule(mix, 40.0, seed)
        for k in range(int(40.0 // arr["burst_every_s"])):
            mid = (k + 0.5) * arr["burst_every_s"]
            lo, hi = mid - arr["burst_spread_s"] / 2, mid + arr["burst_spread_s"] / 2
            assert sum(lo <= p.due <= hi for p in plan) >= per


def test_prompt_tokens_are_reproducible():
    a = TR.prompt_tokens(2 ** 31 + 1, 7, 512, 49155)
    assert a.shape == (1, 512) and a.min() >= 2 and a.max() < 49155
    assert (a == TR.prompt_tokens(2 ** 31 + 1, 7, 512, 49155)).all()
    assert not (a == TR.prompt_tokens(2 ** 31 + 1, 8, 512, 49155)).all()
