"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.akg import plan_attention, plan_matmul
from repro.kernels import ops, ref


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 128),
                                   (64, 256, 512), (32, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_allclose(m, n, k, dtype):
    r = jax.random.PRNGKey(0)
    a = jax.random.normal(r, (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(r, 1), (k, n), dtype)
    got = np.asarray(ops.matmul(a, b, interpret=True), np.float32)
    want = np.asarray(ref.matmul_ref(a, b), np.float32)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * k ** 0.5)


def test_matmul_plan_is_polytops_derived():
    plan = plan_matmul(256, 256, 256)
    assert plan.loop_order[0] == "i"
    assert plan.loop_order[-1] == "j"        # lanes innermost (contiguity)
    assert plan.vector_iter == "j"
    assert plan.tile["j"] % 128 == 0 or plan.tile["j"] == 256


@pytest.mark.parametrize("b,s,h,hkv,d", [(2, 256, 4, 2, 64), (1, 128, 2, 2, 32),
                                         (2, 64, 4, 4, 128)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_allclose(b, s, h, hkv, d, causal):
    r = jax.random.PRNGKey(1)
    q = jax.random.normal(r, (b, s, h, d), jnp.float32) * 0.3
    k = jax.random.normal(jax.random.fold_in(r, 2), (b, s, hkv, d), jnp.float32) * 0.3
    v = jax.random.normal(jax.random.fold_in(r, 3), (b, s, hkv, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, interpret=True)
    rep = h // hkv
    kr, vr = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        kr.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        vr.transpose(0, 2, 1, 3).reshape(b * h, s, d),
        causal=causal).reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,s,di,st", [(2, 64, 256, 16), (1, 128, 128, 8),
                                       (1, 44, 128, 8)])
def test_selective_scan_allclose(b, s, di, st):
    r = jax.random.PRNGKey(2)
    a_bar = jax.nn.sigmoid(jax.random.normal(r, (b, s, di, st))) * 0.9
    b_bar = jax.random.normal(jax.random.fold_in(r, 4), (b, s, di, st)) * 0.1
    c = jax.random.normal(jax.random.fold_in(r, 5), (b, s, st))
    got = ops.selective_scan(a_bar, b_bar, c, interpret=True)
    want = ref.selective_scan_ref(a_bar, b_bar, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_attention_plan_lanes():
    plan = plan_attention(512, 512, 128)
    assert plan.vector_iter == "d"           # head_dim on lanes
    assert plan.tile["q"] <= 128 and plan.tile["kk"] <= 128


# ---------------------------------------------------------------------------
# KernelPlan lowering properties: every kernel's scheduler-produced tree
# lowers to a TPU-legal plan — lane-aligned vector dim, sublane-aligned
# next-inner dim, VMEM-fitting tiles.
# ---------------------------------------------------------------------------

from repro.core.akg import (LANE, SUBLANE, VMEM_BYTES,  # noqa: E402
                            lower_to_kernel_plan, plan_mamba_scan,
                            plan_scan_gate, scan_block_bytes)
from repro.core.cachemodel import (stmt_access_groups,  # noqa: E402
                                   working_set_bytes)


def _assert_tpu_legal(plan, scop, stmt_idx, dims, bytes_per_elem, n_buffers):
    stmt = scop.statements[stmt_idx]
    # grid order covers every iterator exactly once
    assert sorted(plan.loop_order) == sorted(stmt.iters)
    vec = plan.vector_iter
    assert vec in plan.loop_order
    # lane alignment on the vector dim (or the whole dim when small)
    tv = plan.tile[vec]
    assert tv % LANE == 0 or tv == dims[vec], (plan, dims)
    # sublane alignment on the next-inner non-vector dim
    inner = [it for it in plan.loop_order if it != vec]
    if inner:
        ti = plan.tile[inner[-1]]
        assert ti % SUBLANE == 0 or ti == dims[inner[-1]], (plan, dims)
    # the tile working set (real access groups, buffered) fits VMEM
    groups = stmt_access_groups(stmt, list(plan.loop_order))
    sizes = [plan.tile[it] for it in plan.loop_order]
    ws = n_buffers * working_set_bytes(groups, sizes, bytes_per_elem)
    assert ws <= VMEM_BYTES, (plan, ws)


@pytest.mark.parametrize("m,n,k", [(128, 128, 128), (256, 512, 128),
                                   (512, 512, 512), (64, 256, 512),
                                   (1024, 1024, 512), (2048, 2048, 2048)])
def test_matmul_plan_tpu_legal(m, n, k):
    from repro.core.akg import _matmul_scop
    plan = plan_matmul(m, n, k)
    _assert_tpu_legal(plan, _matmul_scop(m, n, k), 0,
                      {"i": m, "j": n, "kk": k}, 2, 3)


@pytest.mark.parametrize("sq,sk,d", [(128, 128, 64), (512, 512, 128),
                                     (256, 1024, 128), (1024, 1024, 64)])
def test_attention_plan_tpu_legal(sq, sk, d):
    plan = plan_attention(sq, sk, d)
    dims = {"q": sq, "kk": sk, "d": d}
    assert plan.vector_iter == "d"
    assert plan.tile["d"] % LANE == 0 or plan.tile["d"] == d
    assert plan.tile["q"] <= 128 and plan.tile["kk"] <= 128
    assert all(plan.tile[it] % SUBLANE == 0 or plan.tile[it] == dims[it]
               for it in plan.loop_order)


@pytest.mark.parametrize("seq,di,st", [(64, 128, 8), (128, 256, 16),
                                       (256, 512, 32), (512, 1024, 16),
                                       (256, 256, 255), (128, 2048, 256)])
def test_mamba_plan_tpu_legal(seq, di, st):
    plan = plan_mamba_scan(seq, di, st)
    # t is the recurrence dim: sequential, outermost in the grid order
    assert plan.loop_order[0] == "t"
    assert plan.tile["n"] == st            # hidden state untiled (VMEM)
    assert plan.tile["d"] % LANE == 0 or plan.tile["d"] == di  # d on lanes
    assert plan.tile["t"] <= seq
    # the pinned state dim counts against the budget: the kernel's real
    # blocks (padded, double-buffered) fit VMEM even for
    # non-lane-multiple states
    assert scan_block_bytes(plan.tile, fused=False) <= VMEM_BYTES, plan
    assert scan_block_bytes(plan_scan_gate(seq, di, st).tile,
                            fused=True) <= VMEM_BYTES


def test_mamba_kernel_consumes_scheduler_plan():
    """selective_scan's default block geometry comes from the schedule
    tree (no hand-coded order/tiles) and still matches the oracle."""
    import repro.kernels.mamba_scan as ms
    plan = plan_mamba_scan(64, 128, 8)
    r = jax.random.PRNGKey(7)
    a_bar = jax.nn.sigmoid(jax.random.normal(r, (1, 64, 128, 8))) * 0.9
    b_bar = jax.random.normal(jax.random.fold_in(r, 1), (1, 64, 128, 8)) * 0.1
    c = jax.random.normal(jax.random.fold_in(r, 2), (1, 64, 8))
    got = ms.selective_scan(a_bar, b_bar, c,     # plan-driven defaults
                            interpret=True)
    explicit = ms.selective_scan(a_bar, b_bar, c,
                                 d_block=plan.tile["d"],
                                 chunk=plan.tile["t"], interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(explicit),
                               rtol=0, atol=0)
    want = ref.selective_scan_ref(a_bar, b_bar, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_plan_wrappers_are_thin_over_general_lowering():
    """plan_matmul is the general tree lowering, nothing more."""
    from repro.core.akg import _matmul_scop
    from repro.core.config import tensor_style
    from repro.core.schedcache import cached_schedule_scop
    from repro.core.schedtree import schedule_tree

    scop = _matmul_scop(256, 256, 256)
    cfg = tensor_style()
    cfg.auto_vectorize = True
    sched = cached_schedule_scop(scop, cfg)
    assert lower_to_kernel_plan(schedule_tree(sched)) == plan_matmul(256, 256, 256)
