#!/usr/bin/env python3
"""One benchmark run: one cell, one seed, one measured window, one line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The run is driven by data. ``BENCHMARK.json`` names the cell; the cell
names a configuration (``bench/configs/<config>.json``, its plain
reference beside it as ``<config>.py``) and a traffic mix
(``bench/traffic/<traffic>.json``). The configuration names its driver
(``bench/drivers/<driver>.py``), which builds the cell on the device from
the seed and warms up every shape the window uses. Each per-layer metric
is a reader of its own (``bench/metrics/<metric>.py``). A new cell,
configuration or metric is new files and new entries, never an edit.

The run measures set-up (process start to the first timed call), then
sends the cell's calls back to back for ``--seconds``: each driver keeps
the chip fed as far as its cell allows (a stencil cell dispatches seconds
of chained calls ahead; a solve waits on the program's own host loop).
When the time is up nothing more is sent, the run waits for all that was
sent, and the window closes after that wait: every call sent counts, over
all of that time. With ``--trace 1`` the
window runs under JAX's profiler and the line carries the per-layer
metrics read from the trace; otherwise it carries the end-to-end metrics.
After the window the peak device memory is read, the program's state is
freed, and the driver compares what the window produced with the plain
reference. The last lines on standard error and the last key of the line
give each number compared beside its limit.

Without a TPU, with fewer chips than the cell asks for, with a device
kind missing from ``bench/peaks.json``, or without the program under
test (``src/repro``), the run prints no result and exits with code 3.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Any, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
REFUSED = 3


class Refused(Exception):
    """The run cannot be made on this machine or in this directory."""


def load_module(path: pathlib.Path):
    """Import the Python file at ``path`` (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """What ``BENCHMARK.json`` and the data files say about one cell."""

    name: str
    chips: int
    config: dict
    config_path: pathlib.Path
    traffic: dict
    end_to_end: list
    per_layer: list
    bench: pathlib.Path


def find_cell(root: pathlib.Path, name: str) -> Cell:
    spec = read_json(root / "BENCHMARK.json")
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    bench = root / spec["paths"][0]
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moves)]
    return Cell(name=name, chips=int(w["chips"]),
                config=read_json(root / c["file"]),
                config_path=root / c["file"],
                traffic=read_json(bench / "traffic" / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer, bench=bench)


def attached_devices(chips: int, peaks: dict, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu:
        if devices[0].platform != "tpu":
            raise Refused(f"no TPU: JAX's backend is {devices[0].platform}")
        if len(devices) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX finds "
                          f"{len(devices)}")
        if devices[0].device_kind not in peaks:
            raise Refused(f"device kind {devices[0].device_kind!r} is not "
                          f"in bench/peaks.json")
    return devices


def enable_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, where that is unset, at the fixed ``.jax_cache/`` of the checkout.
    Every program is cached, however short its compile, so that a second
    run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts compilations: backend compiles that no persistent-cache hit
    served, and the seconds spent in compiles and cache loads."""

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.hits


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader may read about a traced window."""

    trace: Any                 # trace_reduce.Reduction
    calls: int                 # calls completed in the traced window
    window_s: float
    chips: int
    peak: Optional[dict]       # bench/peaks.json entry of the device kind
    info: dict                 # the cell's description of one call


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def _passes(check: dict) -> bool:
    return check["value"] is not None and check["value"] <= check["limit"]


def main(argv=None, *, root: pathlib.Path = ROOT,
         src: Optional[pathlib.Path] = None, require_tpu: bool = True,
         compile_cache: bool = True, t0: Optional[float] = None) -> int:
    """Make one run; returns the exit code. The keywords let a test run
    a copy of the benchmark on the CPU: another root, the program's
    sources, no look for a TPU, no persistent compilation cache."""
    t0 = _T0 if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    ap.add_argument("--control", action="store_true",
                    help="put the reference, computed one precision lower, "
                         "in the program's place (calibration only)")
    args = ap.parse_args(argv)

    src = pathlib.Path(src) if src is not None else root / "src"
    try:
        if not (src / "repro" / "exec" / "__init__.py").is_file():
            raise Refused(f"no program under test at {src}")
        cell = find_cell(root, args.workload)
        peaks = read_json(cell.bench / "peaks.json")
        devices = attached_devices(cell.chips, peaks, require_tpu)
    except Refused as e:
        print(f"run.py: {e}; nothing run", file=sys.stderr)
        return REFUSED

    import jax
    if compile_cache:
        enable_compile_cache(root)
    counter = CompileCounter()
    bench = cell.bench
    for path in (str(src), str(bench)):
        if path not in sys.path:
            sys.path.insert(0, path)
    driver = load_module(bench / "drivers" / f"{cell.config['driver']}.py")
    reference = load_module(cell.config_path.with_suffix(".py"))
    used = devices[:cell.chips]
    work = driver.build(config=cell.config, traffic=cell.traffic,
                        limits=read_json(bench / "limits" / f"{cell.name}.json"),
                        seed=args.seed, devices=used, reference=reference,
                        control=args.control)
    setup_s = time.perf_counter() - t0
    setup_compiles = counter.compiles
    print(f"run.py: {cell.name} seed={args.seed} set-up {setup_s:.3f} s, "
          f"{setup_compiles} compiles, {counter.seconds:.3f} s in compile "
          f"or cache load; {work.describe()}", file=sys.stderr)

    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="bench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # Python frames slow the host
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = counter.compiles
    dispatches = 0
    w0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            work.call()
        dispatches += 1
        if time.perf_counter() - w0 >= args.seconds:
            break
    with jax.profiler.TraceAnnotation("bench.drain"):
        work.drain()
    window_s = time.perf_counter() - w0
    if args.trace:
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - before
    mem = memory_peak_bytes(used)

    checks = work.finish()
    correct = all(_passes(c) for c in checks.values())

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out: dict = {"correct": bool(correct), "attempted": work.attempted,
                 "failed": work.failed}
    if args.trace:
        import trace_reduce
        red = trace_reduce.load(trace_dir, only_devices=[d.id for d in used])
        if args.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = LayerContext(trace=red, calls=work.attempted,
                           window_s=window_s,
                           chips=cell.chips,
                           peak=peaks.get(devices[0].device_kind),
                           info=work.info())
        metrics = {}
        for m in cell.per_layer:
            value = load_module(bench / "metrics" / f"{m['name']}.py").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_s()
        device["window_s"] = window_s
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": red.idle_gaps(10)}
    else:
        values = dict(work.end_to_end(window_s, work.attempted),
                      setup_s=setup_s)
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
        out["device"] = device
    out["window"] = {"seconds": window_s, "dispatches": dispatches,
                     "calls": work.attempted,
                     "compiles_in_window": window_compiles,
                     "setup_compiles": setup_compiles}
    out["checks"] = {k: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for k, c in checks.items()}
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if _passes(c) else 'FAILED'}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
