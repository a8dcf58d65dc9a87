import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x input-shape x mesh) cell this lowers + compiles
the real step function (train_step / prefill / serve_step) against
ShapeDtypeStruct stand-ins — no allocation — and records:

  * ``compiled.memory_analysis()``  (fits-in-HBM evidence)
  * ``compiled.cost_analysis()``    (FLOPs / bytes for the roofline)
  * per-op collective bytes parsed from the post-SPMD HLO
  * the three roofline terms + dominant bottleneck (§Roofline)

Artifacts land in runs/dryrun/<mesh>/<arch>__<shape>.json, consumed by
benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.core.hardware import TPU_V5E
from repro.core import hlo_costs
from repro.core.perf_model import CollectiveStats, roofline_from_analysis
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.lm import Model
from repro.optim import adamw

_HC_F32_MOMENTS = bool(int(os.environ.get("REPRO_F32_MOMENTS", "0")))
from repro.nn import param as nnp
from repro.runtime.steps import make_train_step, make_serve_step


def _input_shardings(model, rules, mesh, specs, axes):
    """NamedShardings for an input_specs pytree using logical axes with
    real-shape divisibility fallback."""
    def one(spec, ax):
        pspec = rules.spec_for(spec.shape, ax, is_param=False, name="input")
        return jax.ShapeDtypeStruct(spec.shape, spec.dtype,
                                    sharding=NamedSharding(mesh, pspec))
    return jax.tree.map(one, specs, axes,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def model_flops_for(cfg, shape, model) -> float:
    """Analytic MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active
    params, D = tokens processed by the step."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Path, save_hlo: bool = False, seq_shard: bool = True,
             fsdp: bool = True, overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "multi" if multi_pod else "single"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind, "status": "ok"}
    ok, reason = applicable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=reason)
        return _write(rec, out_dir)

    out_dir.mkdir(parents=True, exist_ok=True)
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    rules = shd.make_rules(mesh, fsdp=fsdp, seq_shard=seq_shard)
    model = Model(cfg)
    spec_tree = model.params_spec()
    param_structs = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        nnp.spec_tree_structs(spec_tree), rules.param_shardings(spec_tree))

    def shardings_of(structs):
        return jax.tree.map(lambda s: s.sharding, structs,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    with mesh, shd.use_rules(rules):
        if shape.kind == "train":
            opt_cfg = adamw.AdamWConfig(
                moment_dtype=jnp.float32 if _HC_F32_MOMENTS else None)
            opt_spec = adamw.init_spec(opt_cfg, spec_tree)
            opt_structs = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                nnp.spec_tree_structs(opt_spec),
                rules.param_shardings(opt_spec))
            in_specs = model.input_specs(kind="train", seq_len=shape.seq_len,
                                         global_batch=shape.global_batch)
            in_axes = model.batch_logical_axes(kind="train")
            batch_structs = _input_shardings(model, rules, mesh, in_specs,
                                             in_axes)
            step = make_train_step(model, opt_cfg, accum=cfg.train_accum)
            rep = NamedSharding(mesh, P())
            jitted = jax.jit(
                step, donate_argnums=(0, 1),
                out_shardings=(shardings_of(param_structs),
                               shardings_of(opt_structs),
                               {"loss": rep, "grad_norm": rep, "lr": rep}))
            args = (param_structs, opt_structs, batch_structs)
        elif shape.kind == "prefill":
            in_specs = model.input_specs(kind="prefill",
                                         seq_len=shape.seq_len,
                                         global_batch=shape.global_batch)
            in_axes = model.batch_logical_axes(kind="prefill")
            batch_structs = _input_shardings(model, rules, mesh, in_specs,
                                             in_axes)
            # pin the output cache sharding — the compiler otherwise picks a
            # (sometimes replicated) layout for the prefill cache, which at
            # 32k x 80L is itself larger than HBM (EXPERIMENTS.md §Perf)
            cache_structs = _input_shardings(
                model, rules, mesh,
                model.cache_spec(shape.global_batch, shape.seq_len),
                model.cache_logical_axes())
            logits_sh = rules.spec_for(
                (shape.global_batch, cfg.vocab), ("batch", "vocab"),
                is_param=False, name="logits")
            jitted = jax.jit(
                lambda p, b: model.prefill(p, b),
                out_shardings=(NamedSharding(mesh, logits_sh),
                               shardings_of(cache_structs)))
            args = (param_structs, batch_structs)
        else:  # decode
            in_specs = model.input_specs(kind="decode",
                                         seq_len=shape.seq_len,
                                         global_batch=shape.global_batch)
            in_axes = model.batch_logical_axes(kind="decode")
            structs = _input_shardings(model, rules, mesh, in_specs, in_axes)
            logits_sh = rules.spec_for(
                (shape.global_batch, cfg.vocab), ("batch", "vocab"),
                is_param=False, name="logits")
            jitted = jax.jit(
                make_serve_step(model), donate_argnums=(1,),
                out_shardings=(NamedSharding(mesh, logits_sh),
                               shardings_of(structs["cache"])))
            args = (param_structs, structs["cache"], structs["tokens"])

        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        ca = dict(compiled.cost_analysis() or {})
        hlo = compiled.as_text()
        # cost_analysis() counts while bodies once; use the trip-count-
        # corrected HLO accounting instead (see core/hlo_costs.py):
        #   flops — dot flops (exact), floored by scaled cost_analysis;
        #   bytes — 2x materialized-buffer bytes (each non-fusion tensor
        #   written once and read ~once; fusion internals excluded).
        hc = hlo_costs.analyze(hlo)
        ca_flops = float(ca.get("flops", 0.0) or 0.0)
        ca_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        ca["flops"] = max(ca_flops * hc.flops_scale, hc.flops)
        ca["bytes accessed"] = 2.0 * hc.out_bytes
        coll = CollectiveStats(
            {k: int(v) for k, v in hc.coll_bytes.items()},
            {k: int(v) for k, v in hc.coll_count.items()})
        rl = roofline_from_analysis(
            cost_analysis=ca, collective=coll, n_devices=n_dev,
            model_flops=model_flops_for(cfg, shape, model), chip=TPU_V5E)

        rec.update(
            n_devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            flops_scale=round(hc.flops_scale, 2),
            bytes_scale=round(hc.bytes_scale, 2),
            flops_raw_cost_analysis=ca_flops,
            bytes_raw_cost_analysis=ca_bytes,
            flops_per_device=rl.flops_per_device,
            bytes_per_device=rl.bytes_per_device,
            collective_bytes_per_device=rl.collective_bytes_per_device,
            collectives=coll.count_by_op,
            collective_bytes_by_op=coll.bytes_by_op,
            compute_s=rl.compute_s,
            memory_s=rl.memory_s,
            collective_s=rl.collective_s,
            dominant=rl.dominant,
            model_flops=rl.model_flops,
            useful_flops_fraction=round(rl.useful_flops_fraction, 4),
            roofline_fraction=round(rl.roofline_fraction, 4),
            sharding_fallbacks=[f"{n}:{l}({d})" for n, l, d, _ in
                                rules.fallbacks],
        )
        if ma is not None:
            rec["memory"] = {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            }
            live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            rec["memory"]["live_bytes"] = live
            rec["memory"]["fits_v5e_hbm"] = bool(live < TPU_V5E.hbm_bytes)
        if save_hlo:
            (out_dir / f"{arch}__{shape_name}.hlo.txt").write_text(hlo)
    return _write(rec, out_dir)


def _write(rec: dict, out_dir: Path) -> dict:
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{rec['arch']}__{rec['shape']}.json"
    path.write_text(json.dumps(rec, indent=1, default=float))
    status = rec["status"]
    extra = ""
    if status == "ok":
        extra = (f"compile={rec['compile_s']}s dominant={rec['dominant']} "
                 f"rf={rec['roofline_fraction']}")
    elif status == "skip":
        extra = rec["reason"][:60]
    else:
        extra = rec.get("error", "")[:120]
    print(f"[dryrun {rec['mesh']}] {rec['arch']:24s} {rec['shape']:12s} "
          f"{status:5s} {extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--param-dtype", default=None,
                    help="override param dtype (hillclimb variants)")
    ap.add_argument("--set", action="append", default=[],
                    help="cfg override KEY=INTVALUE (hillclimb variants)")
    args = ap.parse_args()

    overrides = {}
    if args.param_dtype:
        overrides["param_dtype"] = dict(bf16=jnp.bfloat16,
                                        f32=jnp.float32)[args.param_dtype]
    for kv in args.set:
        k, v = kv.split("=")
        overrides[k] = int(v)

    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        out = Path(args.out) / ("multi" if mp else "single")
        try:
            rec = run_cell(a, s, multi_pod=mp, out_dir=out,
                           save_hlo=args.save_hlo,
                           seq_shard=not args.no_seq_shard,
                           fsdp=not args.no_fsdp, overrides=overrides)
            if rec["status"] == "error":
                failures += 1
        except Exception as e:  # noqa: BLE001 — record and continue
            failures += 1
            _write({"arch": a, "shape": s,
                    "mesh": "multi" if mp else "single",
                    "kind": SHAPES[s].kind, "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]}, out)
    print(f"dry-run finished: {len(cells) - failures}/{len(cells)} cells ok",
          flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
