"""Serving entrypoint: batched generation over any assigned architecture.

Smoke scale (this CPU container):
  PYTHONPATH=src python -m repro.launch.serve --arch gemma-7b --preset smoke \\
      --requests 8 --max-new 16

Pod scale: the ``decode_32k`` / ``long_500k`` dry-run cells lower exactly the
decode program this engine runs, on the (16,16) and (2,16,16) meshes.
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from ..configs.registry import get_config, get_smoke_config
from .compile_cache import enable_compile_cache
from ..models.model import Model
from ..serve import Request, ServeConfig, ServeEngine


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_config(args.arch) if args.preset == "full"
           else get_smoke_config(args.arch))
    if cfg.is_encdec or cfg.family == "vlm":
        print(f"[serve] note: {args.arch} needs frontend embeddings; the "
              "demo serves its text decoder with token prompts only.",
              flush=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len).tolist(),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    frontend_seq = (8 if (cfg.is_encdec or cfg.family == "vlm") else 0)
    engine = ServeEngine(model, params, ServeConfig(
        batch=args.batch, max_len=args.max_len,
        temperature=args.temperature, seed=args.seed),
        frontend_seq=frontend_seq)
    results = engine.serve(reqs)
    for rid in sorted(results)[:4]:
        print(f"  req {rid}: {results[rid].tokens[:12]} ...", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
