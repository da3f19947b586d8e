"""Work counts and peaks against hand-computed values at the published
widths of granite_3_2b (d 2048, 32/8 heads of 64, ff 8192)."""
import os

import pytest

from harness import spec
from harness import work as WK

CONFIGS = os.path.join(spec.BENCH_DIR, "configs")


def _ref(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "reference",
                                         name + ".py"), "ref_work_" + name)


def test_causal_attention_chunk_at_granite_widths():
    # 256 queries at offset 512: 256*512 + 256*257/2 = 163968 causal pairs
    w = WK.causal_attention(512, 256, 32, 8, 64)
    assert w.flops == 4 * 163968 * 32 * 64 == 1_343_225_856
    # q and out at 32 heads, k and v over 768 keys at 8 heads, bf16
    assert w.bytes == (2 * 256 * 32 * 64 + 2 * 768 * 8 * 64) * 2 == 3_670_016


def test_mlp_matmul_at_granite_widths():
    w = WK.matmul(256, 2048, 8192)
    assert w.flops == 8_589_934_592
    assert w.bytes == (256 * 2048 + 2048 * 8192 + 256 * 8192) * 2 == 38_797_312


def test_least_time_names_the_binding_bound():
    pk = WK.peaks("TPU v5 lite")
    t, bound = WK.least_seconds(WK.matmul(256, 2048, 8192), pk)
    # 43.6 us of compute against 47.4 us of weights: memory binds
    assert bound == "memory" and t == pytest.approx(38_797_312 / 819e9)
    t, bound = WK.least_seconds(WK.matmul(4096, 4096, 4096), pk)
    assert bound == "compute" and t == pytest.approx(2 * 4096 ** 3 / 197e12)


def test_peaks_table_and_unknown_device_kind():
    pk = WK.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        WK.peaks("cpu")


@pytest.mark.parametrize("name,active", [
    # 40 x (attention 10,485,760 + MLP 50,331,648) + head 49155 x 2048
    ("granite_3_2b", 2_533_365_760),
])
def test_active_parameters(name, active):
    conf = spec.load_json(os.path.join(CONFIGS, name + ".json"))
    assert _ref(name).shapes(conf)["active_params"] == active
