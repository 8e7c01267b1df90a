"""The reduction from a profiler trace to device time, kernel time and
idle gaps, on small traces whose answers are known."""
import json
from pathlib import Path

import pytest

from bench import harness, tracing

RECORDED = Path(__file__).parent / "data" / "trace_v5e_small.json"


def synthetic():
    ops = [("fusion.1", 0, 10, 0, "", 10),
           ("fusion.2", 5, 10, 0, "", 10),              # overlaps fusion.1
           ("custom-call.3", 20, 5, 0, "_fused_blend_kernel", 5),
           ("custom-call.4", 38, 10, 0, "_prtu_entry_kernel", 10)]  # clipped
    spans = [("bench.window", 0, 40), ("bench.step", 0, 30),
             ("bench.submit", 30, 10)]
    return tracing.Trace.build(ops, spans)


def test_busy_is_the_union_of_op_intervals_in_the_window():
    t = synthetic()
    assert t.window == (0, 40)
    assert t.busy_intervals(0) == [[0, 15], [20, 25], [38, 40]]
    assert t.busy_s == pytest.approx(22e-9)
    assert t.window_s == pytest.approx(40e-9)


def test_op_seconds_by_kernel():
    t = synthetic()
    assert t.op_seconds("_fused_blend_kernel") == pytest.approx(5e-9)
    assert t.op_seconds("_prtu_entry_kernel") == pytest.approx(2e-9)
    assert t.op_seconds("") == pytest.approx(20e-9)
    assert t.op_seconds() == pytest.approx(27e-9)


def test_top_ops_and_idle_gaps():
    t = synthetic()
    assert t.top_ops(2) == [["fusion.1", pytest.approx(10e-9)],
                            ["fusion.2", pytest.approx(10e-9)]]
    gaps = t.idle_gaps(5)
    assert gaps == [["bench.submit", pytest.approx(13e-9)],
                    ["bench.step", pytest.approx(5e-9)]]


def test_busy_is_averaged_over_devices():
    ops = [("a", 0, 10, 0, "", 10), ("a", 0, 30, 1, "", 30)]
    t = tracing.Trace.build(ops, [("bench.window", 0, 40)])
    assert t.devices == 2
    assert t.busy_s == pytest.approx(20e-9)


def test_nested_ops_count_once():
    # A loop event spans its body's events: self times add up to busy time.
    events = [(0, 100), (10, 20), (40, 30), (50, 5), (120, 10)]
    assert tracing._self_times(events) == [50.0, 20.0, 25.0, 5.0, 10.0]
    names = ["while.1", "fusion.2", "custom-call.3", "fusion.4", "copy.5"]
    ops = [(n, s, d, 0, "_fused_blend_kernel" if n == "custom-call.3"
            else "", own) for n, (s, d), own in
           zip(names, events, tracing._self_times(events))]
    t = tracing.Trace.build(ops, [("bench.window", 0, 140)])
    assert t.op_seconds() == pytest.approx(t.busy_s) == pytest.approx(110e-9)
    assert t.op_seconds("_fused_blend_kernel") == pytest.approx(25e-9)
    assert t.top_ops(1) == [["while.1", pytest.approx(50e-9)]]


def test_op_labels():
    assert tracing.describe(
        "%while.38 = (s32[]{:T(128)}, s32[5,4,500,4096]{2,1,3,0:T(4,128)}) "
        "while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b"
    ) == "while.38 = (s32[], s32[5,4,500,4096]) while"
    assert tracing.describe(
        "%fusion.48 = pred[8192,64]{1,0:T(8,128)(4,1)S(1)} fusion(pred[2] "
        "%a), kind=kCustom") == "fusion.48 = pred[8192,64] fusion"


def test_instruction_names():
    assert tracing.instruction(
        "%fusion.48 = pred[8192,64]{1,0} fusion(pred[2] %a), kind=kCustom"
    ) == "fusion.48"
    assert tracing.instruction("ROOT %tuple.7 = (f32[]) tuple()") == \
        "tuple.7"
    assert tracing.instruction("no instruction") == "no instruction"


def test_kernel_map_reads_the_mosaic_body():
    import base64
    body = base64.b64encode(b"ML\x00kernels\x00_fused_blend_kernel.<locals>"
                            b"._init\x00_fused_blend_kernel\x00_blend_block"
                            b"\x00").decode()
    hlo = ("  %fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop\n"
           "  %closed_call.105 = (f32[64,8,256]{2,1,0}) custom-call(%a), "
           'custom_call_target="tpu_custom_call", backend_config={'
           '"custom_call_config":{"body":"' + body + '"}}\n'
           "  %custom-call.9 = f32[2]{0} custom-call(%b), "
           'custom_call_target="tpu_custom_call", backend_config={}\n')
    assert tracing.kernel_map(hlo) == {
        "closed_call.105": "_fused_blend_kernel",
        "custom-call.9": "tpu_custom_call"}


def test_json_round_trip(tmp_path):
    t = synthetic()
    t.to_json(tmp_path / "t.json")
    back = tracing.Trace.from_json(tmp_path / "t.json")
    assert back.busy_s == t.busy_s and back.top_ops() == t.top_ops()


def test_readers_on_the_synthetic_trace():
    class Run:
        trace = synthetic()
        served = [object(), object()]
    r = {m: harness.reader(m)(Run) for m in (
        "device_idle_share", "xla_ms_per_frame",
        "blend_kernel_ms_per_frame", "ctu_kernel_ms_per_frame")}
    assert r["device_idle_share"] == pytest.approx(100 * (1 - 22 / 40))
    assert r["xla_ms_per_frame"] == pytest.approx(1e3 * 20e-9 / 2)
    assert r["blend_kernel_ms_per_frame"] == pytest.approx(1e3 * 5e-9 / 2)
    assert r["ctu_kernel_ms_per_frame"] == pytest.approx(1e3 * 2e-9 / 2)


def test_readers_find_nothing_without_a_trace():
    class Run:
        trace = None
        served = [object()]
    for m in ("device_idle_share", "xla_ms_per_frame",
              "blend_kernel_ms_per_frame", "ctu_kernel_ms_per_frame"):
        assert harness.reader(m)(Run) is None


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e of a 128x128 two-frame batch of the
    served path: both Pallas kernels are found by name, and the shares add
    up."""
    t = tracing.Trace.from_json(RECORDED)
    with open(RECORDED.with_suffix(".expect.json")) as f:
        expect = json.load(f)
    assert t.op_seconds("_fused_blend_kernel") > 0
    assert t.op_seconds("_prtu_entry_kernel") > 0
    assert 0 < t.busy_s <= t.window_s
    assert t.op_seconds() >= t.busy_s
    for key, value in expect.items():
        assert getattr(t, key) == pytest.approx(value, rel=1e-9)
