"""The reduction from a profiler trace to device times, on a synthetic
trace whose every number is known, and on a trace recorded on a v5e."""
import pathlib

import pytest
from jax.profiler import ProfileData

import trace_reduce
from conftest import BENCH

# Device 0 (times in us after the line's 1 us timestamp): a kernel 0-5, a
# while 5.5-9.5 holding a fusion 5.5-6.5 and a collective 7-8.5, a kernel
# 11-12. Device 1: one fusion 0-4. The host: a call span over everything,
# a wait span 10-13 (over device 0's gap 10.5-12).
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 6 offset_ps: 5500000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1500000 }
    events { metadata_id: 4 offset_ps: 11000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 0 duration_ps: 12000000 } }
  event_metadata { key: 1 value { id: 1 name: "%stencil_perks_deep.3 = f32[64,128]{1,0:T(8,128)} custom-call(f32[64,128]{1,0:T(8,128)} %copy.4)" } }
  event_metadata { key: 2 value { id: 2 name: "collective-permute-done.1" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p)" } }
  event_metadata { key: 4 value { id: 4 name: "stencil_perks_deep.3" } }
  event_metadata { key: 5 value { id: 5 name: "jit_timed" } }
  event_metadata { key: 6 value { id: 6 name: "%while = (s32[]) while(s32[] %t), condition=%c, body=%b" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.7" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 11000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "bench.wait" } }
}
'''


@pytest.fixture
def red():
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def test_devices_host_and_hlo_names(red):
    assert [d.index for d in red.devices] == [0, 1]
    assert len(red.host) == 2
    assert [s.name for s in red.devices[0].ops] == [
        "stencil_perks_deep.3", "while", "fusion.12",
        "collective-permute-done.1", "stencil_perks_deep.3"]


def test_busy_is_the_union_averaged_over_devices(red):
    # device 0: 5 + 4 + 1 = 10 us; device 1: 4 us
    assert red.devices[0].busy_ns() == 10000
    assert red.devices[1].busy_ns() == 4000
    assert red.busy_s() == pytest.approx(7e-6, rel=1e-12)


def test_op_time_by_name_prefix(red):
    # 6 us on device 0, none on device 1
    assert red.op_s("stencil_perks") == pytest.approx(3e-6, rel=1e-12)
    assert red.op_s("no_such_kernel") is None


def test_collective_time(red):
    assert red.collective_s() == pytest.approx(0.75e-6, rel=1e-12)


def test_self_time_excludes_nested_ops(red):
    assert red.devices[0].self_ns() == {
        "stencil_perks_deep.3": 6000, "while": 1500, "fusion.12": 1000,
        "collective-permute-done.1": 1500}


def test_top_ops_by_self_time_drop_numeric_suffix(red):
    top = red.top_ops(10)
    assert [name for name, _ in top] == [
        "stencil_perks_deep", "fusion", "collective-permute-done", "while"]
    assert [t for _, t in top] == pytest.approx(
        [3e-6, 2.5e-6, 0.75e-6, 0.75e-6], rel=1e-12)
    assert red.top_ops(1) == top[:1]


def test_idle_gaps_named_by_innermost_host_span(red):
    gaps = red.idle_gaps(10)
    # device 0 idles 6-6.5 us (host: call) and 10.5-12 us (host: wait)
    assert gaps == [["bench.wait", pytest.approx(1.5e-6, rel=1e-12)],
                    ["bench.call", pytest.approx(0.5e-6, rel=1e-12)]]


def test_only_devices_keeps_the_cells_own(tmp_path):
    path = tmp_path / "t.xplane.pb"
    # a ProfileData cannot be written back; filter the reduction instead
    red = trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    red.devices = [d for d in red.devices if d.index in {1}]
    assert red.busy_s() == pytest.approx(4e-6, rel=1e-12)
    assert red.op_s("stencil_perks") is None
    assert trace_reduce.load(tmp_path).devices == []
    assert not path.exists()


def test_union_of_nested_and_touching_spans():
    S = trace_reduce.Span
    spans = [S("a", 0, 10), S("b", 2, 4), S("c", 10, 12), S("d", 20, 21)]
    assert trace_reduce.union(spans) == [(0, 12), (20, 21)]


def test_recorded_v5e_trace():
    """A traced window of jacobi2d5pt.stream on one TPU v5e: 11 calls of
    the deep kernel, 256 steps each."""
    red = trace_reduce.load(BENCH / "data" / "stream_v5e.xplane.pb",
                            only_devices=[0])
    assert [d.index for d in red.devices] == [0]
    assert red.busy_s() == pytest.approx(3.268912186, rel=1e-9)
    assert red.op_s("stencil_perks") == pytest.approx(3.249729687, rel=1e-9)
    assert len([s for s in red.devices[0].ops
                if s.name.startswith("stencil_perks_deep")]) == 11
    assert red.top_ops(1)[0][0] == "stencil_perks_deep"
    assert red.collective_s() is None
    assert trace_reduce.load(BENCH / "data" / "stream_v5e.xplane.pb",
                             only_devices=[3]).devices == []


def test_metric_readers_on_recorded_v5e_trace():
    """Each stencil reader on the recorded window: 11 calls of 256 steps
    of the 16384x8192 field in a 3.2833614680000096 s window."""
    import json

    import run
    import work
    red = trace_reduce.load(BENCH / "data" / "stream_v5e.xplane.pb",
                            only_devices=[0])
    peak = json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]
    call = work.stencil_call((16384, 8192), 5, 256, 4)
    ctx = run.LayerContext(trace=red, calls=11, window_s=3.2833614680000096,
                           chips=1, peak=peak,
                           info={"kernel_prefix": "stencil_perks",
                                 "steps_per_call": 256,
                                 "work_per_call": call})

    def read(name):
        return run.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)

    least = 11 * call.bytes / 819e9
    assert read("device_idle_pct.stencil") == pytest.approx(
        100 * (1 - 3.268912186 / 3.2833614680000096), rel=1e-9)
    assert read("kernel_ms_per_step.stencil") == pytest.approx(
        1e3 * 3.249729687 / (11 * 256), rel=1e-9)
    assert read("kernel_roofline_pct.stencil") == pytest.approx(
        100 * least / 3.249729687, rel=1e-9)
    assert read("mfu_pct.stencil") == pytest.approx(
        100 * least / 3.2833614680000096, rel=1e-9)
