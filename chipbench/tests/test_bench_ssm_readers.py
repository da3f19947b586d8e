"""The selective scan's work count, checked by hand, and the readers of
the SSM cell: ``scan_gate_roofline`` and ``ssm_inputs_share`` with the
whole-name-stack helper, on hand-made traces."""
import os
from types import SimpleNamespace

import pytest

from harness import scope_paths as SP
from harness import spec
from harness import trace as TRC
from harness import engine_spans as ES
from harness import work_ssm as WS


def _reader(name):
    return spec.reader(name)


def test_selective_scan_work_by_hand():
    # 8 rows, 4 channels, state 2: 8*4*(8*2 + 5) = 672 operations;
    # x, dt, z, y 4 x 8*4 and B, C 2 x 8*2 bf16 = 320 bytes; A, h0,
    # h_last 3 x 4*2 and D 4 in f32 = 112 bytes
    w = WS.selective_scan(8, 4, 2)
    assert w.flops == 672
    assert w.bytes == (4 * 32 + 2 * 16) * 2 + (3 * 8 + 4) * 4 == 432


def test_selective_scan_work_at_falcon_widths():
    # one layer, a 256-row chunk, d_inner 8192, state 16
    w = WS.selective_scan(256, 8192, 16)
    assert w.flops == 256 * 8192 * 133 == 278_921_216
    assert w.bytes == (4 * 256 * 8192 + 2 * 256 * 16) * 2 \
        + (3 * 8192 * 16 + 8192) * 4 == 18_399_232


def test_scan_gate_roofline_counts_every_ssm_layer():
    seen = {}

    def kernel_share(needle, per_chunk):
        seen["needle"] = needle
        seen["work"] = per_chunk(512, 256)
        return 7.0
    r = SimpleNamespace(shapes={"ssm": {"layers": 32, "d_inner": 8192,
                                        "state": 16}},
                        kernel_share=kernel_share)
    assert _reader("scan_gate_roofline").read(r) == 7.0
    assert seen["needle"] == "scan_gate"
    assert seen["work"].bytes == 32 * 18_399_232
    # a model with no SSM layer has nothing to read
    assert _reader("scan_gate_roofline").read(
        SimpleNamespace(shapes={"ssm": None}, kernel_share=kernel_share)) \
        is None


@pytest.mark.parametrize("path, want", [
    ("jit(_chunk_tick)/kv_cache/while/body/closed_call/layer/ssm/"
     "ssm_inputs/exp:", ["jit(_chunk_tick)", "kv_cache", "while", "body",
                         "closed_call", "layer", "ssm", "ssm_inputs"]),
    ("jit(_decode_tick)/sample/argmax:", ["jit(_decode_tick)", "sample"]),
    ("", []),
])
def test_components_of_a_name_stack(path, want):
    assert SP.components(path) == want


def _paths():
    # window 0..100: a layer loop 0..80 holding an ssm_inputs fusion
    # 10..30, the kernel 30..60 and a decode-side ssm fusion 60..70; an
    # ssm_inputs op 90..110 that the window cuts; idle 80..90
    ops = [("while.1", 0, 80, ""), ("fusion.1", 10, 30, ""),
           ("scan_gate", 30, 60, ""), ("fusion.2", 60, 70, ""),
           ("fusion.3", 90, 110, "")]
    loop = "jit(_mixed_tick)/kv_cache/while:"
    inner = "jit(_mixed_tick)/kv_cache/while/body/closed_call/layer/ssm/"
    paths = [loop, inner + "ssm_inputs/mul:", inner + "pallas_call:",
             inner + "add:", inner + "ssm_inputs/exp:"]
    return SP.Paths(TRC.Trace(ops, [(TRC.WINDOW, 0, 100)]), paths)


def test_ssm_inputs_time_goes_to_the_innermost_op():
    p = _paths()
    # 10..30 and 90..100; the loop's own time is not the scope's
    assert SP.seconds_in(p, "ssm_inputs") == pytest.approx(30e-9)
    # every leaf is in ssm: 10..70 and 90..100
    assert SP.seconds_in(p, "ssm") == pytest.approx(70e-9)
    assert p.red.busy_s == pytest.approx(90e-9)
    # a program with no such scope has nothing to read
    assert SP.seconds_in(p, "attention") is None


def test_ssm_inputs_share_of_busy_time():
    r = SimpleNamespace(trace=object(), scope_paths=_paths())
    assert _reader("ssm_inputs_share").read(r) == pytest.approx(
        100.0 * 30 / 90)
    assert _reader("ssm_inputs_share").read(SimpleNamespace(trace=None)) \
        is None


def _xplane(directory):
    """A hand-made profile: chip 0's two ops with their name stacks, and
    the benchmark's window on a host thread."""
    XSpace = ES._xspace()
    sp = XSpace()
    dev = sp.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=7).value.name = "tf_op"
    for k, (name, tf) in enumerate([
            ("%fusion.1 = f32[1,256,8192,16]{3,2,1,0} fusion(%p)",
             "jit(_chunk_tick)/kv_cache/while/body/layer/ssm/ssm_inputs/exp:"),
            ("%scan_gate.3 = (bf16[1,256,8192]) custom-call(%a)",
             "jit(_chunk_tick)/kv_cache/while/body/layer/ssm/pallas_call:")],
            start=1):
        md = dev.event_metadata.add(key=k).value
        md.name = name
        md.stats.add(metadata_id=7, str_value=tf)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    ops.events.add(metadata_id=1, offset_ps=0, duration_ps=20_000)
    ops.events.add(metadata_id=2, offset_ps=20_000, duration_ps=60_000)
    host = sp.planes.add(name="/host:CPU")
    host.event_metadata.add(key=1).value.name = TRC.WINDOW
    line = host.lines.add(name="python", timestamp_ns=1000)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=100_000)
    d = os.path.join(directory, "plugins", "profile", "run")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(sp.SerializeToString())


def test_load_keeps_each_ops_whole_name_stack(tmp_path):
    _xplane(str(tmp_path))
    p = SP.load(str(tmp_path))
    assert [o[0] for o in p.trace.ops] == ["fusion.1", "scan_gate.3"]
    assert p.paths[0].endswith("/ssm/ssm_inputs/exp:")
    assert p.red.window_s == pytest.approx(100e-9)
    assert SP.seconds_in(p, "ssm_inputs") == pytest.approx(20e-9)
    assert p.red.op_seconds("scan_gate") == pytest.approx(60e-9)
    # engine_spans reads the same file but keeps only the innermost of
    # its fixed scopes: ssm_inputs is lost there
    assert ES.load(str(tmp_path)).scopes == ["ssm", "ssm"]
