#!/usr/bin/env python3
"""Readings that set a cell's limits: whole runs of ``run.py`` on many
seeds, for the program, for the control and for planted faults, in one
process (one chip, one start-up).

    python3 bench/calibrate.py --workload <cell> --seconds 5 \
        --seeds 1,2,... --control-seeds 101,102,103 \
        --fault dropped_step --fault-seeds 201,202,203

Each run is ``run.main`` as the benchmark makes it; its result line is
printed with ``seed``, ``control`` and ``fault`` added in front. The
control (``--control``) puts the configuration's plain reference,
computed one precision lower, in the program's place. A fault is planted
in the program for its seeds only (``FAULTS``). Both have to come out
over the limits, which are set between the readings by hand in
``bench/limits/<cell>.json``. The benchmark's own runs run neither.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402


def _dropped_step(dispatch):
    """Each stencil call advances one step fewer than it was asked to."""
    from repro.exec import StencilProblem

    def broken(problem, plan, *rest):
        if isinstance(problem, StencilProblem):
            problem = dataclasses.replace(problem,
                                          n_steps=problem.n_steps - 1)
        return dispatch(problem, plan, *rest)
    return broken


#: Faults planted in the executor's tier dispatch, by name.
FAULTS = {"dropped_step": _dropped_step}


@contextlib.contextmanager
def planted(fault):
    """The program with ``fault`` planted in its dispatch."""
    from repro.exec import executor
    dispatch = executor._dispatch
    executor._dispatch = FAULTS[fault](dispatch)
    try:
        yield
    finally:
        executor._dispatch = dispatch


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()

    runs = [(s, False, None) for s in _seeds(args.seeds)]
    runs += [(s, True, None) for s in _seeds(args.control_seeds)]
    runs += [(s, False, args.fault) for s in _seeds(args.fault_seeds)]
    for seed, control, fault in runs:
        argv = ["--workload", args.workload, "--seed", str(seed),
                "--seconds", args.seconds] + (["--control"] if control
                                              else [])
        out = io.StringIO()
        with contextlib.redirect_stdout(out), (
                planted(fault) if fault else contextlib.nullcontext()):
            code = run.main(argv, t0=time.perf_counter())
        if code:
            return code
        line = json.loads(out.getvalue().strip().splitlines()[-1])
        print(json.dumps(dict(seed=seed, control=control, fault=fault,
                              **line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
