"""Serving launcher: batched requests through the PERKS persistent-decode
engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
      --requests 8 --new-tokens 32
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.registry import get_config, get_smoke_config
from repro.core.compile_cache import enable_compile_cache
from repro.core.hardware import attached_chip
from repro.models.lm import Model
from repro.runtime.server import Engine, Request, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--host-loop", action="store_true",
                    help="baseline per-token dispatch instead of PERKS")
    args = ap.parse_args()
    enable_compile_cache()

    chip = attached_chip()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    eng = Engine(model, params, ServeConfig(
        max_batch=args.requests, persistent=not args.host_loop))

    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(Request(
            prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                dtype=np.int32),
            max_new_tokens=args.new_tokens))
    toks, stats = eng.run_batch()
    print("chip:", chip.name, "generated:", toks.shape)
    for k, v in stats.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
