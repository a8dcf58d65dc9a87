"""Fixtures for the benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests

``tiny_root`` is a copy of the benchmark in a temporary directory whose
cells keep their names, configurations and limits but run small traffic
mixes that the CPU holds; the program comes from the repository's
``src``."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent.parent
REPO = BENCH.parent
SRC = REPO / "src"

for path in (str(BENCH), str(SRC)):
    if path not in sys.path:
        sys.path.insert(0, path)

#: Small traffic for each committed traffic mix.
TINY = {
    "stream": {"domain": [64, 256], "steps_per_call": 8,
               "calls_per_dispatch": 1, "dispatch_ahead_s": 0.05},
    "resident": {"domain": [32, 128], "steps_per_call": 8,
                 "calls_per_dispatch": 3, "dispatch_ahead_s": 0.05},
    "small": {"grid_side": 16},
}


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "data",
                                                  "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", dest / "BENCHMARK.json")
    for name, traffic in TINY.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(traffic))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def run_cell(root, workload, *extra, seed=2**33 + 1, seconds=0.2, trace=0,
             capsys=None):
    """One run of ``bench/run.py`` in-process; returns (exit code, the
    result line as a dict or None)."""
    import run
    code = run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace),
                     *extra],
                    root=pathlib.Path(root), src=SRC, require_tpu=False,
                    compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines() if capsys else []
    return code, (json.loads(out[-1]) if out else None)
