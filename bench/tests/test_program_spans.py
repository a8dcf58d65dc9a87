"""Device idle time put down to the program's spans: on a synthetic trace
whose every number is known, on CPU runs of the cells, and with the Krylov
readers on a trace recorded on a v5e."""
import gzip
import json

import pytest
from jax.profiler import ProfileData

import program_spans
import run
import trace_reduce
from conftest import BENCH, run_cell

# Device 0 (times in us after the lines' 1 us timestamp) runs 0-2, 5-6,
# 9-10 and 14-15, so it idles 2-5, 6-9 and 10-14. The host: the
# harness's call 0-20 (no program span), and a dispatch 1-13 holding a
# compile 1.5-4, a barrier 4-7, a chunk 7.5-9.5 and a barrier 9.5-11.
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 14000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.3" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 12000000 }
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 2500000 }
    events { metadata_id: 4 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 7500000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 9500000 duration_ps: 1500000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.call" } }
  event_metadata { key: 2 value { id: 2 name: "repro.dispatch" } }
  event_metadata { key: 3 value { id: 3 name: "repro.compile" } }
  event_metadata { key: 4 value { id: 4 name: "repro.barrier" } }
  event_metadata { key: 5 value { id: 5 name: "repro.chunk" } }
}
'''
WINDOW_S = 20e-6
READERS = ["idle_compile_pct.krylov", "idle_sync_pct.krylov",
           "idle_outside_execute_pct.krylov", "chunks_per_solve.krylov"]


@pytest.fixture
def red():
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))


def _ctx(red, solves=1):
    return run.LayerContext(trace=red, calls=solves, window_s=WINDOW_S,
                            chips=1, peak=None, info={"solves": solves})


def _read(name, ctx):
    return run.load_module(BENCH / "metrics" / f"{name}.py").read(ctx)


def test_idle_goes_to_the_innermost_program_span(red):
    # gap 2-5: compile 2-4, barrier 4-5 (split where the compile ends);
    # gap 6-9: barrier 6-7, the dispatch alone 7-7.5, chunk 7.5-9;
    # gap 10-14: barrier 10-11, the dispatch alone 11-13, no program span
    # 13-14 (the harness's span does not count)
    assert program_spans.idle_by_span(red) == {
        "repro.compile": 2000, "repro.barrier": 3000, "repro.chunk": 1500,
        "repro.dispatch": 2500, None: 1000}
    assert sum(program_spans.idle_by_span(red).values()) == sum(
        b - a for a, b in red.devices[0].gaps())


def test_span_counts(red):
    assert program_spans.span_counts(red) == {
        "repro.dispatch": 1, "repro.compile": 1, "repro.barrier": 2,
        "repro.chunk": 1}


def test_idle_before_and_after_every_span():
    S = trace_reduce.Span
    red = trace_reduce.Reduction(
        [trace_reduce.Device(0, [S("a", 0, 10), S("b", 20, 30),
                                 S("c", 40, 50), S("d", 60, 70)])],
        [S("repro.dispatch", 25, 45), S("repro.barrier", 44, 44)])
    # 10-20 before every span, 30-40 in the dispatch, 50-60 after it
    assert program_spans.idle_by_span(red) == {None: 20,
                                               "repro.dispatch": 10}


def test_readers_on_the_synthetic_trace(red):
    ctx = _ctx(red)
    assert _read("idle_compile_pct.krylov", ctx) == pytest.approx(
        100 * 2e-6 / WINDOW_S, rel=1e-12)
    assert _read("idle_sync_pct.krylov", ctx) == pytest.approx(
        100 * 4.5e-6 / WINDOW_S, rel=1e-12)
    assert _read("idle_outside_execute_pct.krylov", ctx) == pytest.approx(
        100 * 1e-6 / WINDOW_S, rel=1e-12)
    assert _read("chunks_per_solve.krylov", _ctx(red, solves=2)) == 1.0


def test_readers_find_nothing_without_program_spans(red):
    """The trace of a program that emits no ``repro.*`` span."""
    red.host = [s for s in red.host if not s.name.startswith("repro.")]
    assert program_spans.idle_by_span(red) is None
    assert all(_read(name, _ctx(red)) is None for name in READERS)


def _traced(tiny_root, tmp_path, capsys, workload):
    trace_dir = tmp_path / "trace"
    code, line = run_cell(tiny_root, workload, "--trace-dir",
                          str(trace_dir), trace=1, capsys=capsys)
    assert code == 0 and line["correct"] is True
    return line, trace_reduce.load(trace_dir)


def test_cg_cell_traced_on_the_cpu(tiny_root, tmp_path, capsys):
    """A traced CPU run of the CG cell: one ``repro.dispatch`` a solve in
    the window. The CPU's trace has no device plane, so the readers find
    nothing to read and the line leaves them out."""
    line, red = _traced(tiny_root, tmp_path, capsys, "cg-poisson2d.small")
    assert red.devices == []
    assert program_spans.span_counts(red)["repro.dispatch"] == \
        line["attempted"]
    assert not set(READERS) & set(line["metrics"])


@pytest.mark.parametrize("workload",
                         ["jacobi2d5pt.stream", "jacobi2d5pt.resident"])
def test_stencil_windows_hold_no_program_span(tiny_root, tmp_path, capsys,
                                              workload):
    """The stencil cells run ``execute`` under ``jit``: its spans fire
    while it is traced in set-up, and no timed call crosses one."""
    line, red = _traced(tiny_root, tmp_path, capsys, workload)
    assert program_spans.program_spans(red) == []
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    assert not set(READERS) & set(line["metrics"])
    assert all(workload not in m.get("workloads", [])
               for m in spec["per_layer"] if m["name"] in READERS)


def test_readers_on_recorded_v5e_trace(tmp_path):
    """A traced window of cg-poisson2d.small on one TPU v5e: one solve of
    500 iterations, 20 dispatches and host syncs, in 1.3143516769999977 s
    (gzipped to keep the repository small)."""
    path = tmp_path / "cg_v5e.xplane.pb"
    path.write_bytes(gzip.decompress(
        (BENCH / "data" / "cg_v5e.xplane.pb.gz").read_bytes()))
    red = trace_reduce.load(path, only_devices=[0])
    window_s = 1.3143516769999977
    ctx = run.LayerContext(trace=red, calls=1, window_s=window_s, chips=1,
                           peak=None, info={"solves": 1})
    assert program_spans.span_counts(red) == {
        "repro.dispatch": 1, "repro.compile": 1, "repro.chunk": 19,
        "repro.barrier": 20}
    m = {name: _read(name, ctx) for name in READERS}
    assert m == pytest.approx({
        "idle_compile_pct.krylov": 5.322063890819697,
        "idle_sync_pct.krylov": 2.2363512379799744,
        "idle_outside_execute_pct.krylov": 0.14942005510143264,
        "chunks_per_solve.krylov": 20.0}, rel=1e-9)
    # with the dispatch's own idle and the edges before the first and
    # after the last operation, the shares make up the device's idle
    ops = red.devices[0].ops
    edges_s = window_s - 1e-9 * (max(s.end_ns for s in ops)
                                 - min(s.start_ns for s in ops))
    dispatch = 100 * 1e-9 * program_spans.idle_by_span(red)[
        "repro.dispatch"] / window_s
    assert (sum(m[n] for n in READERS[:3]) + dispatch
            + 100 * edges_s / window_s) == pytest.approx(
        _read("device_idle_pct.krylov", ctx), rel=1e-9)
