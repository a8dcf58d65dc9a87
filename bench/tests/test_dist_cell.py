"""The four-chip cell ``jacobi2d5pt.dist4`` on four virtual CPU devices at
a tiny domain: a sound run, the control and two planted faults (a dropped
step, a halo exchange that sends zeros), each a whole ``run.py`` run in
one child process (the faults are ``calibrate_dist.py``'s); and its two
per-layer readers on a synthetic trace."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.profiler import ProfileData

import run
import trace_reduce
from conftest import BENCH, SRC, make_tiny_root
from test_trace_reduce import SYNTHETIC

#: The cell's traffic at a size the CPU holds: 16-row shards, an 8-step
#: halo each side.
TINY_DIST4 = {"domain": [64, 256], "steps_per_call": 8,
              "calls_per_dispatch": 1, "dispatch_ahead_s": 0.05}

#: Each run of the child: ``run.main`` with the program as it is, under
#: ``--control``, or with a fault planted. The chip is the CPU stand-in
#: with its HBM cut to 128 KiB, which one shard's fields and temporaries
#: fit (about 80 KiB) and the whole 64 KiB field's do not (192 KiB), so
#: the planner has to shard it.
CHILD = """
import contextlib, dataclasses, io, json, pathlib, sys
sys.path[:0] = [{bench!r}, {src!r}]
import calibrate, calibrate_dist, run
from repro.core import hardware
small = dataclasses.replace(hardware.CPU_INTERPRET, hbm_bytes=128 * 1024)
hardware.attached_chip = lambda: small

runs = {{"sound": ([], contextlib.nullcontext()),
        "control": (["--control"], contextlib.nullcontext()),
        "dropped_step": ([], calibrate.planted("dropped_step")),
        "no_halo": ([], calibrate.planted("no_halo"))}}
out = {{}}
for name, (extra, fault) in runs.items():
    buf = io.StringIO()
    with fault, contextlib.redirect_stdout(buf):
        code = run.main(["--workload", "jacobi2d5pt.dist4",
                         "--seed", str(2**33 + 7), "--seconds", "0.2",
                         "--trace", "0", *extra],
                        root=pathlib.Path({root!r}), src=pathlib.Path({src!r}),
                        require_tpu=False, compile_cache=False)
    lines = buf.getvalue().strip().splitlines()
    out[name] = {{"code": code, "line": json.loads(lines[-1]) if lines
                  else None}}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("dist4"))
    (root / "bench" / "traffic" / "dist4.json").write_text(
        json.dumps(TINY_DIST4))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CHILD.format(
            bench=str(root / "bench"), src=str(SRC), root=str(root)))],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_sound_run_is_correct_on_the_distributed_tier(runs):
    out, stderr = runs
    code, line = out["sound"]["code"], out["sound"]["line"]
    assert code == 0 and line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) == {"setup_s", "stencil_gcells_s"}
    assert "tier=distributed shards=4 shard_rows=16" in stderr


@pytest.mark.parametrize("fault", ["control", "dropped_step", "no_halo"])
def test_control_and_faults_are_not_correct(runs, fault):
    out, _ = runs
    code, line = out[fault]["code"], out[fault]["line"]
    assert code == 0 and line["correct"] is False
    fresh = line["checks"]["max_abs_err.fresh_call"]
    assert fresh["value"] > fresh["limit"]


def test_one_chip_plan_fails_at_set_up(tmp_path, capsys):
    """A planner that keeps the field on one chip (the CPU stand-in's 16
    GiB hold it) is refused before the window, with nothing printed."""
    root = make_tiny_root(tmp_path)
    (root / "bench" / "traffic" / "dist4.json").write_text(
        json.dumps(TINY_DIST4))
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(f"""
            import pathlib, sys
            sys.path[:0] = [{str(root / 'bench')!r}, {str(SRC)!r}]
            import run
            sys.exit(run.main(["--workload", "jacobi2d5pt.dist4", "--seed",
                               "1", "--seconds", "0.2", "--trace", "0"],
                              root=pathlib.Path({str(root)!r}),
                              src=pathlib.Path({str(SRC)!r}),
                              require_tpu=False, compile_cache=False))
        """)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode != 0 and proc.stdout == ""
    assert "not distributed" in proc.stderr


def _ctx(calls=2, steps=5):
    red = trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    return run.LayerContext(trace=red, calls=calls, window_s=20e-6, chips=2,
                            peak=None, info={"steps_per_call": steps})


def test_collective_share_reads_collective_ops():
    # device 0 ran a 1.5 us collective, device 1 none: 0.75 us of 20
    read = run.load_module(BENCH / "metrics" / "collective_pct.dist.py").read
    assert read(_ctx()) == pytest.approx(100 * 0.75 / 20)


def test_shard_ms_per_step_leaves_collectives_out():
    # busy 10 us less 1.5 us of collective on device 0, 4 us on device 1:
    # 6.25 us a device over 2 calls of 5 steps
    read = run.load_module(
        BENCH / "metrics" / "shard_ms_per_step.dist.py").read
    assert read(_ctx()) == pytest.approx(6.25e-3 / 10)
