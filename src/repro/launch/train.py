"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 50 --ckpt-dir runs/ckpt

Full-size archs on real hardware use the production mesh + sharding rules;
on this CPU container use --smoke (reduced config, local devices).
"""
from __future__ import annotations

import argparse


from repro.configs.registry import get_config, get_smoke_config
from repro.core.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig
from repro.dist import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.lm import Model
from repro.optim import adamw
from repro.runtime.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--steps-per-dispatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    opt_cfg = adamw.AdamWConfig(lr=args.lr, total_steps=args.steps)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    tc = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every, accum=args.accum,
                       steps_per_dispatch=args.steps_per_dispatch)
    trainer = Trainer(model, opt_cfg, data_cfg, tc)

    if args.production_mesh:
        mesh = make_production_mesh()
        rules = shd.make_rules(mesh)
        with mesh, shd.use_rules(rules):
            trainer.run()
    else:
        trainer.run()


if __name__ == "__main__":
    main()
