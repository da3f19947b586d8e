"""The program's own spans and scopes: a traced run of the small chat
cell records ``ContinuousEngine``'s spans inside the benchmark's; the
per-tick host time, the idle split by innermost span and the device
split by scope, checked on hand-made traces."""
import pytest

import bench_tiny
from harness import cell as C
from harness import engine_spans as ES
from harness import trace as TRC


def test_traced_run_nests_engine_spans_in_bench_ticks():
    sess = C.Session(bench_tiny.cell("granite_chat"), 2 ** 31 + 5,
                     check_device=False)
    win = sess.window(2.0, True)
    assert win["trace"] is not None
    spans = ES.load(C.TRACE_DIR).trace.spans

    def named(n):
        return [(s, e) for m, s, e in spans if m == n]

    def each_within(inner, outer):
        return all(any(a <= s and e <= b for a, b in named(outer))
                   for s, e in named(inner))
    for n in ("engine.tick", "engine.admit", "engine.dispatch",
              "engine.fetch", "engine.retire"):
        assert named(n), n
    assert each_within("engine.tick", "bench.tick")
    for n in ("engine.admit", "engine.dispatch", "engine.retire",
              "engine.fetch"):
        assert each_within(n, "engine.tick"), n
    assert each_within("engine.fetch", "engine.retire")


def _hand():
    # window 0..100 ns: a tick 2..58 (admit 2..5, dispatch 5..15, retire
    # 15..58 holding a fetch 20..50) inside bench.tick 0..60, a wait
    # 60..100, a tick 95..105 that the window cuts; device busy 12..45
    # and 70..80
    return TRC.Trace(
        ops=[("fusion.1", 12, 45, ""), ("copy.1", 70, 80, "")],
        spans=[(TRC.WINDOW, 0, 100), ("bench.tick", 0, 60),
               ("engine.tick", 2, 58), ("engine.admit", 2, 5),
               ("engine.dispatch", 5, 15), ("engine.retire", 15, 58),
               ("engine.fetch", 20, 50), ("bench.wait", 60, 95),
               ("bench.tick", 95, 105), ("engine.tick", 96, 105)])


def test_tick_host_time_leaves_out_the_fetch():
    red = TRC.Reduced(_hand())
    # 56 ns of tick less 30 of fetch; the cut tick is left out
    assert ES.tick_host_ms(red) == [pytest.approx(26e-6)]


def test_idle_goes_to_the_innermost_span_at_each_instant():
    got = dict(ES.idle_by_span(TRC.Reduced(_hand())))
    # gaps 0..12, 45..70, 80..100
    assert got["engine.dispatch"] == pytest.approx(7e-9)   # 5..12
    assert got["engine.admit"] == pytest.approx(3e-9)
    assert got["engine.fetch"] == pytest.approx(5e-9)      # 45..50
    assert got["engine.retire"] == pytest.approx(8e-9)     # 50..58
    assert got["bench.tick"] == pytest.approx(5e-9)        # 0..2, 58..60, 95..96
    assert got["bench.wait"] == pytest.approx(25e-9)       # 60..70, 80..95
    assert got["engine.tick"] == pytest.approx(4e-9)       # 96..100
    assert sum(got.values()) == pytest.approx(57e-9)


def test_device_time_by_scope_counts_each_instant_once():
    # a layer-scan loop 0..100 holding scoped leaves; a leaf with no
    # name stack after it
    tr = TRC.Trace(
        ops=[("while.1", 0, 100, ""), ("fusion.1", 10, 40, ""),
             ("fusion.2", 40, 70, ""), ("copy.1", 70, 90, ""),
             ("copy.2", 110, 120, "")],
        spans=[(TRC.WINDOW, 0, 130)])
    prof = ES.Profile(tr, [ES.UNSCOPED, "attention", "mlp", "kv_cache",
                           ES.UNSCOPED])
    by = ES.device_by_op(prof)
    assert by[("attention", "fusion.1")] == pytest.approx(30e-9)
    assert by[("mlp", "fusion.2")] == pytest.approx(30e-9)
    assert by[("kv_cache", "copy.1")] == pytest.approx(20e-9)
    # the loop's own time is what its leaves leave: 0..10 and 90..100
    assert by[(ES.UNSCOPED, "while.1")] == pytest.approx(20e-9)
    assert by[(ES.UNSCOPED, "copy.2")] == pytest.approx(10e-9)
    assert sum(by.values()) == pytest.approx(prof.red.busy_s)
    notes = ES.notes(prof)
    assert any("attention 3" in n and "unscoped 3" in n for n in notes)


@pytest.mark.parametrize("text, want", [
    ('%fusion.7 = bf16[256,2048]{1,0} fusion(%p0, %p1), kind=kOutput, '
     'calls=%fused_computation.7, metadata={op_name="jit(_mixed_tick)/'
     'jit(main)/kv_cache/while/body/closed_call/layer/attention/'
     'dot_general" source_file="attention.py" source_line=70}',
     "attention"),
    ("jit(_decode_tick)/kv_cache/while:", "kv_cache"),
    ("jit(_decode_tick)/kv_cache/while/body/closed_call/layer/mlp/"
     "pallas_call:", "mlp"),
    ("jit(_chunk_tick)/sample/argmax:", "sample"),
    ("jit(_chunk_tick)/while/body/add:", ES.UNSCOPED),
    ("%copy.49 = bf16[40,16,2048,8,64]{4,3,2,1,0} copy(%p)", ES.UNSCOPED),
])
def test_scope_from_name_stack(text, want):
    assert ES.scope(text) == want
