"""Training runtime: loss decreases, checkpoint round-trip, fault
tolerance, data determinism, gradient compression."""
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim import adamw, grad_compress
from repro.optim.adamw import AdamWConfig
from repro.train import checkpoint as CKPT
from repro.train import fault as FAULT
from repro.train.loop import Trainer, TrainConfig


@pytest.fixture(scope="module")
def tiny_cfg():
    arch = get_arch("granite_3_2b").smoke()
    return TrainConfig(arch=arch, total_steps=25, global_batch=4, seq_len=64,
                       ckpt_every=10, log_every=100,
                       opt=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=25))


def test_loss_decreases(tiny_cfg):
    tr = Trainer(tiny_cfg)
    tr.fit()
    losses = [h["loss"] for h in tr.history]
    assert losses[-1] < losses[0] - 0.05, losses


def test_checkpoint_roundtrip(tiny_cfg):
    with tempfile.TemporaryDirectory() as td:
        key = jax.random.PRNGKey(0)
        from repro.model import transformer as T
        params = T.init_params(key, tiny_cfg.arch)
        opt = adamw.init(params)
        CKPT.save(td, 7, params, opt)
        assert CKPT.latest_step(td) == 7
        p2, o2, meta = CKPT.restore(td)
        assert meta["step"] == 7
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        # keep-N garbage collection
        for s in (8, 9, 10, 11):
            CKPT.save(td, s, params, opt, keep=2)
        steps = sorted(int(p.name.split("_")[1]) for p in Path(td).iterdir())
        assert steps == [10, 11]


def test_preemption_restore(tiny_cfg):
    with tempfile.TemporaryDirectory() as td:
        cfg = TrainConfig(**{**tiny_cfg.__dict__, "ckpt_dir": td,
                             "total_steps": 22, "ckpt_every": 5})
        tr = Trainer(cfg)
        orig = tr.run_step
        fired = {}

        def flaky(step):
            if step == 12 and "f" not in fired:
                fired["f"] = True
                raise FAULT.Preemption("simulated")
            return orig(step)

        tr.run_step = flaky
        out = tr.fit()
        assert out["restarts"] == 1
        assert out["final_step"] == 22


def test_straggler_monitor():
    mon = FAULT.StragglerMonitor(threshold=2.0)
    assert not mon.observe(0, 1.0)
    assert not mon.observe(1, 1.1)
    assert mon.observe(2, 5.0)
    assert mon.flagged == [2]


def test_restart_storm_exhausts_budget():
    """A persistent fault must exhaust max_restarts and surface as a
    RuntimeError chained from the Preemption — not loop forever."""
    calls = {"n": 0}

    def doomed(step):
        calls["n"] += 1
        raise FAULT.Preemption(f"storm {calls['n']}")

    policy = FAULT.FaultPolicy(max_restarts=3)
    with pytest.raises(RuntimeError, match="exceeded max_restarts=3") as ei:
        FAULT.run_resilient(doomed, 0, 10, restore_fn=lambda: 0,
                            save_fn=lambda s: None, policy=policy,
                            log_fn=lambda m: None)
    assert isinstance(ei.value.__cause__, FAULT.Preemption)
    # max_restarts restores + the final fatal attempt
    assert calls["n"] == policy.max_restarts + 1


def test_checkpoint_cadence_and_rewind():
    """Checkpoints land at every multiple of checkpoint_every; a
    preemption rewinds to the latest one and replays the gap."""
    saved, executed = [], []

    def step_fn(step):
        executed.append(step)
        if step == 7 and executed.count(7) == 1:
            raise FAULT.Preemption("simulated")
        return {"step": step}

    policy = FAULT.FaultPolicy(max_restarts=2, checkpoint_every=3)
    out = FAULT.run_resilient(step_fn, 0, 10,
                              restore_fn=lambda: saved[-1],
                              save_fn=saved.append, policy=policy,
                              log_fn=lambda m: None)
    # save_fn(step+1) fires when (step+1) % every == 0
    assert saved == [3, 6, 9]
    # steps 6..7 re-executed after restoring the step-6 checkpoint
    assert executed == [0, 1, 2, 3, 4, 5, 6, 7, 6, 7, 8, 9]
    assert out["restarts"] == 1 and out["final_step"] == 10
    assert out["last_metrics"] == {"step": 9}


def test_straggler_ewma_math():
    """The EWMA recurrence itself: seed on first sample, then
    (1-a)*ewma + a*dt, with the flag judged against the PRE-update mean."""
    mon = FAULT.StragglerMonitor(alpha=0.5, threshold=2.0)
    assert not mon.observe(0, 1.0)        # seeds, can never flag
    assert mon.ewma == 1.0
    assert not mon.observe(1, 2.0)        # 2.0 == 2.0*1.0, not strictly >
    assert mon.ewma == pytest.approx(1.5)
    assert mon.observe(2, 3.1)            # 3.1 > 2.0*1.5
    assert mon.ewma == pytest.approx(2.3)
    # the slow sample raised the mean, so the same reading passes now
    assert not mon.observe(3, 3.1)
    assert mon.flagged == [2]


def test_data_determinism_and_sharding():
    cfg = DataConfig(vocab=100, seq_len=32, global_batch=4, seed=7)
    a = SyntheticLM(cfg).batch(3)
    b = SyntheticLM(cfg).batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # two hosts produce disjoint halves of the same global batch
    h0 = SyntheticLM(DataConfig(vocab=100, seq_len=32, global_batch=4,
                                seed=7, host_id=0, n_hosts=2)).batch(3)
    h1 = SyntheticLM(DataConfig(vocab=100, seq_len=32, global_batch=4,
                                seed=7, host_id=1, n_hosts=2)).batch(3)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), a["tokens"])
    # labels are shifted tokens
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_grad_compress_error_feedback():
    """bf16 compression with feedback is unbiased over repeated steps."""
    g = jnp.full((64,), 0.1001, jnp.float32)   # not bf16-representable
    res = grad_compress.init_residual({"w": g})["w"] * 0
    total = jnp.zeros_like(g)
    r = res
    for _ in range(64):
        q, r = grad_compress.compress_with_feedback({"w": g}, {"w": r})
        q, r = q["w"], r["w"]
        total = total + q.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(total / 64), np.asarray(g),
                               rtol=1e-3)


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=1, total_steps=200,
                      weight_decay=0.0, grad_clip=10.0)
    params = {"w": jnp.array([5.0, -3.0])}
    state = adamw.init(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}       # d/dw (w²)
        params, state, _ = adamw.update(cfg, grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_serve_engine_smoke():
    import jax
    from repro.launch.serve import ContinuousEngine, Request
    from repro.model import transformer as T
    cfg = get_arch("granite_3_2b").smoke()
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    eng = ContinuousEngine(cfg, params, batch=2, max_len=24, chunk=8,
                           max_new=5)
    reqs = [Request(i, jax.random.randint(jax.random.fold_in(key, i),
                                          (1, 8), 2, cfg.vocab))
            for i in range(2)]
    for req in reqs:
        eng.submit(req)
    eng.run()
    for req in reqs:
        assert req.done
        assert len(req.generated) == 5
        assert all(0 <= t < cfg.vocab for t in req.generated)
