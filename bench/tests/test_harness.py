"""The harness end to end on the CPU at small sizes: refusals, sound runs,
the control, planted faults, and a cell and metric added from files."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import BENCH, REPO, SRC, run_cell

ONE_CHIP = ["jacobi2d5pt.stream", "jacobi2d5pt.resident", "cg-poisson2d.small"]


def test_no_tpu_prints_nothing_and_fails(capsys):
    code = run.main(["--workload", "jacobi2d5pt.stream", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], src=SRC)
    assert code == run.REFUSED
    assert capsys.readouterr().out == ""


def test_benchmark_alone_prints_nothing_and_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "jacobi2d5pt.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct(tiny_root, capsys, workload, trace):
    code, line = run_cell(tiny_root, workload, trace=trace, capsys=capsys)
    assert code == 0 and line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        want = {m["name"] for m in spec["end_to_end"]
                if workload in m.get("workloads", [workload])}
        assert set(line["metrics"]) == want
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


def test_every_call_sent_counts(tiny_root, capsys):
    """Chained calls go out several to a dispatch; every one sent is
    counted, over the window that ends when all have completed."""
    code, line = run_cell(tiny_root, "jacobi2d5pt.resident", capsys=capsys)
    window = line["window"]
    assert code == 0 and line["correct"] is True
    assert line["attempted"] == window["calls"] == 3 * window["dispatches"]
    cells_steps = 32 * 128 * 8 * line["attempted"]
    assert line["metrics"]["stencil_gcells_s"]["value"] == pytest.approx(
        cells_steps / window["seconds"] / 1e9)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_is_not_correct(tiny_root, capsys, workload):
    code, line = run_cell(tiny_root, workload, "--control", capsys=capsys)
    assert code == 0 and line["correct"] is False


def _unchanged(problem, plan, mesh, on_sync, tracer, track):
    return problem.finalize(problem.initial_state())


def _altered(dispatch):
    def altered(problem, plan, *rest):
        out = dispatch(problem, plan, *rest)
        if isinstance(out, tuple):          # CG: (x, rr)
            return (out[0].at[0].add(1.0),) + out[1:]
        return out.at[3, 5].add(1e-2)
    return altered


@pytest.mark.parametrize("workload", ONE_CHIP)
@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_planted_fault_is_not_correct(tiny_root, capsys, monkeypatch,
                                      workload, fault):
    from repro.exec import executor
    broken = (_unchanged if fault == "state_unchanged"
              else _altered(executor._dispatch))
    monkeypatch.setattr(executor, "_dispatch", broken)
    code, line = run_cell(tiny_root, workload, capsys=capsys)
    assert code == 0 and line["correct"] is False


def test_dropped_step_is_not_correct(tiny_root, capsys):
    """A stencil call that advances one step fewer than asked for."""
    import calibrate
    with calibrate.planted("dropped_step"):
        code, line = run_cell(tiny_root, "jacobi2d5pt.stream", capsys=capsys)
    assert code == 0 and line["correct"] is False
    assert line["checks"]["max_abs_err.fresh_call"]["value"] > line[
        "checks"]["max_abs_err.fresh_call"]["limit"]


def test_solve_stopped_at_the_cap_is_not_correct(tiny_root, capsys):
    """A solve whose iteration cap comes before the tolerance counts as
    failed, and a failed solve makes the run not correct."""
    path = tiny_root / "bench" / "configs" / "cg-poisson2d.json"
    cfg = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cfg, max_iters=3)))
    code, line = run_cell(tiny_root, "cg-poisson2d.small", capsys=capsys)
    assert code == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] >= 1
    assert line["checks"]["failed_solves"]["value"] == line["failed"]


def test_cell_and_metric_added_as_files(tiny_root, capsys):
    """A new traffic mix, cell, limits and per-layer metric: new files and
    new BENCHMARK.json entries, no existing file edited."""
    bench = tiny_root / "bench"
    (bench / "traffic" / "narrow.json").write_text(json.dumps(
        {"domain": [40, 128], "steps_per_call": 4,
         "calls_per_dispatch": 1, "dispatch_ahead_s": 0.05}))
    (bench / "limits" / "jacobi2d5pt.narrow.json").write_text(
        json.dumps({"max_abs_err": 1e-4}))
    (bench / "metrics" / "calls_traced.narrow.py").write_text(
        "def read(ctx):\n    return float(ctx.calls)\n")
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "jacobi2d5pt.narrow", "config": "jacobi2d5pt",
        "traffic": "narrow", "chips": 1, "why": "a test cell"})
    spec["end_to_end"][1]["workloads"].append("jacobi2d5pt.narrow")
    spec["per_layer"].append({
        "name": "calls_traced.narrow", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "entry", "moves": "stencil_gcells_s",
        "workloads": ["jacobi2d5pt.narrow"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))

    code, line = run_cell(tiny_root, "jacobi2d5pt.narrow", trace=1,
                          capsys=capsys)
    assert code == 0 and line["correct"] is True
    assert line["metrics"]["calls_traced.narrow"]["value"] == line[
        "attempted"]
    code, line = run_cell(tiny_root, "jacobi2d5pt.narrow", capsys=capsys)
    assert set(line["metrics"]) == {"setup_s", "stencil_gcells_s"}


def test_same_seed_same_inputs_large_seeds_differ():
    import jax
    import seeding
    a, b = seeding.key(2**33 + 5), seeding.key(2**33 + 5)
    assert (jax.random.key_data(a) == jax.random.key_data(b)).all()
    c = seeding.key(5)
    assert not (jax.random.key_data(a) == jax.random.key_data(c)).all()
    assert seeding.rng(2**40, 1).random() == seeding.rng(2**40, 1).random()
    assert seeding.worst([1.0, float("nan"), 2.0]) == float("inf")
    assert seeding.worst([1.0, 3.0]) == 3.0
