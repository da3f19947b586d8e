"""The Pallas kernels compile with Mosaic for a TPU v5e at published
widths (``interpret=False``), against a described — not attached —
``v5e:2x2`` topology.  Nothing runs: these tests catch tiling and VMEM
refusals before any chip time is spent.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import mamba_scan as ms
from repro.kernels import matmul_polytops as mm
from repro.kernels import ops
from repro.kernels import scan_gate as sg


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep the cache out of these tests
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _mosaic_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles_at_granite_widths(spec):
    """A 256-row prefill chunk against a 2048-row KV prefix, 32 q heads
    over 8 kv heads, head_dim 64, bf16, with the chunk's q offset."""
    bf = jnp.bfloat16
    text = _mosaic_text(
        lambda q, k, v, off: ops.flash_attention(q, k, v, q_offset=off,
                                                 interpret=False),
        spec((1, 256, 32, 64), bf), spec((1, 2048, 8, 64), bf),
        spec((1, 2048, 8, 64), bf), spec((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,k,n", [(256, 2048, 8192), (256, 8192, 2048)])
def test_planned_matmul_compiles_at_granite_widths(spec, m, k, n):
    """The MLP's up/gate and down projections of a 256-row chunk."""
    bf = jnp.bfloat16
    text = _mosaic_text(lambda a, b: mm.matmul(a, b, interpret=False),
                        spec((m, k), bf), spec((k, n), bf))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_planned_matmul_compiles_at_its_largest_fitted_tiles(spec, dtype):
    """gemma3_4b's MLP down projection over 2048 rows: the fitted tile
    is among the largest the VMEM budget allows, and Mosaic's scoped
    VMEM holds it for bf16 and f32 operands alike."""
    m, k, n = 2048, 10240, 2560
    text = _mosaic_text(lambda a, b: mm.matmul(a, b, interpret=False),
                        spec((m, k), dtype), spec((k, n), dtype))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq", [256, 44])
def test_scan_gate_compiles_at_falcon_mamba_widths(spec, seq):
    """Fused discretisation + scan + gate over a prefill chunk at d_inner
    8192, state 16: Δ, A, B, C and the state in f32, x and z in bf16 as
    the model hands them over, with the state carry; 44 rows is a ragged
    last chunk."""
    f32, bf = jnp.float32, jnp.bfloat16
    di, st = 8192, 16
    text = _mosaic_text(
        lambda dt, A, B, C, x, d, z, h: sg.scan_gate(
            dt, A, B, C, x, d, z, h0=h, interpret=False),
        spec((1, seq, di), f32), spec((di, st), f32),
        spec((1, seq, st), f32), spec((1, seq, st), f32),
        spec((1, seq, di), bf), spec((di,), f32), spec((1, seq, di), bf),
        spec((1, di, st), f32))
    assert "tpu_custom_call" in text


def test_selective_scan_compiles_at_falcon_mamba_widths(spec):
    f32 = jnp.float32
    seq, di, st = 256, 8192, 16
    text = _mosaic_text(
        lambda a, b, c: ms.selective_scan(a, b, c, interpret=False),
        spec((1, seq, di, st), f32), spec((1, seq, di, st), f32),
        spec((1, seq, st), f32))
    assert "tpu_custom_call" in text


def test_kernel_mode_defaults_to_interpret_on_cpu_only(monkeypatch):
    from repro.kernels import _mode
    assert _mode.resolve_interpret(None) is (jax.default_backend() == "cpu")
    assert _mode.resolve_interpret(False) is False
    assert _mode.resolve_interpret(True) is True
    assert fa.flash_attention.__defaults__[-1] is None
    monkeypatch.setattr(_mode.jax, "default_backend", lambda: "tpu")
    assert _mode.resolve_interpret(None) is False
    monkeypatch.setattr(_mode.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError):
        _mode.resolve_interpret(None)
