#!/usr/bin/env python3
"""The plain XLA baseline of the one-chip stencil cells, run once.

    python3 bench/baseline_xla.py --seed 7 --seconds 5

For each one-chip stencil cell it runs the same chained calls as the cell
with two plain XLA sweeps, no Pallas kernel and nothing kept in VMEM: the
program's ``Plan(tier="device_loop")`` (its per-step step function inside
one ``fori_loop``) and the configuration's plain reference (``run`` of
``bench/configs/<config>.py``, slicing and one update per step). It prints
the Gcell/s each reaches over ``--seconds``: the numbers the PERKS kernels
have to beat. They are not metrics, and no change is judged by them.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import seeding  # noqa: E402

CELLS = ("jacobi2d5pt.stream", "jacobi2d5pt.resident")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    root = BENCH.parent
    try:
        devices = run.attached_devices(
            1, run.read_json(BENCH / "peaks.json"), True)
    except run.Refused as e:
        print(f"baseline_xla.py: {e}; nothing run", file=sys.stderr)
        return run.REFUSED
    run.enable_compile_cache(root)
    sys.path.insert(0, str(root / "src"))
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.exec import Plan, StencilProblem, execute
    from repro.kernels.common import get_spec

    for name in CELLS:
        cell = run.find_cell(root, name)
        shape = tuple(cell.traffic["domain"])
        steps = int(cell.traffic["steps_per_call"])
        spec = get_spec(cell.config["stencil"])
        dev = devices[0]
        x = jax.jit(lambda k: jax.random.uniform(k, shape, jnp.float32),
                    out_shardings=SingleDeviceSharding(dev))(
                        seeding.key(args.seed))
        reference = run.load_module(cell.config_path.with_suffix(".py"))
        p = Plan(tier="device_loop")
        sweeps = {
            "device_loop": jax.jit(
                lambda a: execute(StencilProblem(a, spec, steps), p)),
            "reference": lambda a: reference.run(a, steps=steps),
        }
        for sweep, fn in sweeps.items():
            x = jax.block_until_ready(fn(x))
            calls, w0 = 0, time.perf_counter()
            while time.perf_counter() - w0 < args.seconds:
                x = jax.block_until_ready(fn(x))
                calls += 1
            window_s = time.perf_counter() - w0
            print(json.dumps({
                "cell": name, "sweep": sweep, "calls": calls,
                "window_s": window_s,
                "gcells_s": math.prod(shape) * steps * calls / window_s
                / 1e9}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
