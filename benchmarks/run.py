"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  stencil_large_*   Fig. 5  (large-domain stencils, host vs PERKS)
  stencil_small_*   Fig. 6  (small domains — fully VMEM-resident regime)
  stencil_fuse_*    beyond-paper: temporal blocking sweep (fuse_steps in
                    {1,2,4}; DESIGN.md §4, arXiv:2306.03336)
  cg_dataset_*      Fig. 7/9 (SuiteSparse-proxy registry: IMP/VEC/MIX
                    sweep + planner policy + ELL/SELL fill ratios)
  cg_format_*       beyond-paper: SELL-C-σ vs ELL CG on irregular data
  cg_*              Fig. 7  (legacy synthetic suite, host vs PERKS)
  krylov_*          beyond-paper: the Krylov family (DESIGN.md §10) —
                    BiCGStab/GMRES(m) tier sweeps on the nonsymmetric
                    registry, collective counts (textbook vs pipelined vs
                    s-step), mixed-precision overhead + refinement
  where_cache_*     Fig. 8  (where/how much to cache sweep)
  what_cache_*      Fig. 9  (what to cache: CG policy matrix)
  concurrency_*     Table II (occupancy/working-set analog)
  exec_plan_*       beyond-paper: unified-executor autotune — planner-
                    predicted vs measured time per candidate Plan
                    (DESIGN.md §7); the chosen Plan JSON lands in
                    $REPRO_PLAN_JSON when set
  batch_*           beyond-paper: batched multi-tenant execution — per-
                    instance time of one B-wide dispatch vs a sequential
                    per-user loop (DESIGN.md §8)
  service_*         beyond-paper: continuous-batching async engine vs the
                    static drain() path — steady-state per-instance
                    throughput + p50/p99 latency under a Poisson arrival
                    trace (DESIGN.md §9)
  decode_*          beyond-paper: persistent LM decode vs host loop;
                    decode_exec_* serves the same decode through the
                    executor (DecodeAttentionProblem) and ssm_exec_*
                    autotunes the SSD scan as an SSMScanProblem
                    (DESIGN.md §13)
  train_fused_*     beyond-paper: K optimizer steps per dispatch
  roofline_*        §Roofline cells from the dry-run artifacts (if present)

Use REPRO_BENCH_FULL=1 for the full sweep (default trims to keep the run
a few minutes on one CPU core). ``--sections stencil,cg`` (or env
REPRO_BENCH_SECTIONS) runs a subset; ``--chip tpu_v5p`` re-projects the
model-derived columns for another chip (core/hardware.py CHIPS). The CSV
schema and the full bench-section <-> paper-figure mapping are
documented in docs/BENCHMARKS.md.

Observability (DESIGN.md §11): ``--trace PATH`` (env REPRO_TRACE)
installs an ambient ``repro.obs.Tracer`` for the whole run and writes
``PATH`` as Chrome trace-event JSON (load it in Perfetto) plus
``PATH.jsonl`` as raw JSON-lines; ``--ledger PATH`` (env REPRO_LEDGER)
installs a persisted ``DriftLedger`` so every autotuned measurement is
recorded — rerunning against the same ledger skips re-measuring plans it
already knows on this chip/jax version.
"""
from __future__ import annotations

import argparse
import os
import sys

# Runnable both as `python benchmarks/run.py` and `python -m benchmarks.run`:
# the former puts benchmarks/ (not the repo root) on sys.path.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SECTIONS = ("stencil", "fuse", "cg", "krylov", "policy", "exec", "batch",
            "service", "decode", "train", "roofline")


def _parse_sections(text: str) -> set[str]:
    if not text:
        return set(SECTIONS)
    picked = {s.strip() for s in text.split(",") if s.strip()}
    unknown = picked - set(SECTIONS)
    if unknown:
        raise SystemExit(f"unknown sections {sorted(unknown)}; "
                         f"choose from {','.join(SECTIONS)}")
    return picked


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sections", default=os.environ.get(
        "REPRO_BENCH_SECTIONS", ""),
        help=f"comma-separated subset of {','.join(SECTIONS)} "
             "(default: all; env REPRO_BENCH_SECTIONS)")
    ap.add_argument("--chip", default="tpu_v5e",
                    help="chip for model-projected columns "
                         "(core/hardware.py CHIPS)")
    ap.add_argument("--trace", default=os.environ.get("REPRO_TRACE", ""),
                    help="write a Chrome trace-event JSON of the whole run "
                         "here (plus .jsonl raw events; env REPRO_TRACE)")
    ap.add_argument("--ledger", default=os.environ.get("REPRO_LEDGER", ""),
                    help="persist autotune measurements to this drift-"
                         "ledger JSON (env REPRO_LEDGER)")
    args = ap.parse_args(argv)
    sections = _parse_sections(args.sections)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    quick = os.environ.get("REPRO_BENCH_FULL", "0") != "1"
    from benchmarks import stencil_bench, cg_bench, policy_bench, decode_bench
    from benchmarks import batch_bench, exec_bench, train_bench
    from benchmarks.util import row
    from repro import obs
    from repro.core.hardware import CHIPS

    if args.chip not in CHIPS:
        raise SystemExit(f"unknown chip {args.chip!r}; "
                         f"choose from {sorted(CHIPS)}")
    chip = CHIPS[args.chip]

    tracer = None
    if args.trace:
        tracer = obs.Tracer()
        obs.set_tracer(tracer)
    if args.ledger:
        obs.set_ledger(obs.DriftLedger(args.ledger))

    print("name,us_per_call,derived")
    geomeans = {}
    if "stencil" in sections:
        geomeans["stencil_large"] = stencil_bench.run("large", quick=quick,
                                                      chip=chip)
        geomeans["stencil_small"] = stencil_bench.run("small", quick=quick,
                                                      chip=chip)
    if "fuse" in sections:
        stencil_bench.run_fused(quick=quick)
    if "cg" in sections:
        geomeans["cg"] = cg_bench.run(quick=quick, chip=chip)
    if "krylov" in sections:
        from benchmarks import krylov_bench
        geomeans["krylov"] = krylov_bench.run(quick=quick, chip=chip)
    if "policy" in sections:
        policy_bench.run_where(chip=chip)
        policy_bench.run_what(chip=chip)
        policy_bench.run_concurrency(chip=chip)
    if "exec" in sections:
        exec_bench.run(quick=quick, chip=chip)
    if "batch" in sections:
        geomeans["batch"] = batch_bench.run(quick=quick, chip=chip)
    if "service" in sections:
        from benchmarks import service_bench
        geomeans["service"] = service_bench.run(quick=quick, chip=chip)
    if "decode" in sections:
        geomeans["decode"] = decode_bench.run(
            archs=("qwen2-0.5b", "mamba2-780m") if quick
            else ("qwen2-0.5b", "h2o-danube-1.8b",
                  "mamba2-780m", "zamba2-1.2b"))
    if "train" in sections:
        train_bench.run(quick=quick)

    if "roofline" in sections:
        try:
            from benchmarks import roofline
            roofline.csv_rows("single")
        except Exception as e:  # dry-run artifacts may not exist yet
            row("roofline_missing", 0.0, f"run launch.dryrun first ({e})")

    if geomeans:
        row("summary_geomeans", 0.0,
            ";".join(f"{k}={v:.2f}x" for k, v in geomeans.items()))

    if tracer is not None:
        tracer.write_chrome(args.trace)
        tracer.write_jsonl(args.trace + ".jsonl")


if __name__ == "__main__":
    main()
