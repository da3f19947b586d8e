"""``correct`` comes out false when the timed path is broken underneath:
the harness runs whole (warm-up, window, check) at a small width with
the chip's look skipped, and the program's decode step is planted with
each fault a serving cell can have.  One chip, so no exchange between
chips to leave out.  A program compiled inside the window (the warm-up
left out) fails it too."""
import jax
import jax.numpy as jnp
import pytest

import bench_tiny


def _token_altered(orig):
    def step(*a, **k):
        logits, cache = orig(*a, **k)
        return jnp.roll(logits, 1, axis=-1), cache     # each slot's token + 1
    return step


def _state_unchanged(orig):
    def step(params, cfg, token, cache, *a, **k):
        logits, _ = orig(params, cfg, token, cache, *a, **k)
        return logits, cache                           # the cache not written
    return step


def _half_batch(orig):
    def step(*a, **k):
        logits, cache = orig(*a, **k)
        h = logits.shape[0] // 2        # the first half of the slots left
        return jnp.concatenate([logits[h:], logits[h:]]), cache  # out
    return step


FAULTS = {"token_altered": _token_altered, "state_unchanged": _state_unchanged,
          "half_batch_left_out": _half_batch}


@pytest.mark.parametrize("name", sorted(bench_tiny.CELLS))
def test_sound_run_is_correct(name):
    res = bench_tiny.run(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(bench_tiny.CELLS))
def test_planted_fault_is_not_correct(monkeypatch, name, fault):
    from repro.model import transformer as T
    monkeypatch.setattr(T, "serve_decode_step",
                        FAULTS[fault](T.serve_decode_step))
    res = bench_tiny.run(name)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", sorted(bench_tiny.CELLS))
def test_compile_in_window_is_not_correct(monkeypatch, name):
    from harness import serving as SV

    def no_warm_up(*a, **k):
        jax.clear_caches()       # nothing compiled before the window
        return 0
    monkeypatch.setattr(SV, "warm", no_warm_up)
    res = bench_tiny.run(name)
    assert res["checks"]["compiles_in_window"]["value"] > 0
    assert not res["correct"], res["checks"]
