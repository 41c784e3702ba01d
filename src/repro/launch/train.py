"""Production trainer entrypoint.

Config-driven training with the full substrate: sharded step (pjit path),
deterministic prefetching pipeline, async sharded checkpoints with
preemption handling (SIGTERM → checkpoint → clean exit), restore-and-resume
(elastic across mesh shapes), gradient accumulation, LR schedule.

Smoke scale (this CPU container):
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \\
      --preset smoke --steps 20 --global-batch 8 --seq 128

Pod scale (the dry-run proves these configs compile for (16,16) and
(2,16,16) meshes):
  python -m repro.launch.train --arch qwen2-72b --preset full \\
      --mesh 16x16 --steps 100000 --ckpt-dir gs://...
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointConfig, CheckpointManager
from ..configs.registry import get_config, get_smoke_config
from ..data import DataConfig, SyntheticLM, Prefetcher
from ..models.model import Model
from ..optim import AdamW, AdamWConfig
from ..optim.schedule import cosine_warmup
from ..parallel.sharding import axis_rules
from ..train.specs import batch_names, param_names
from ..train.steps import (auto_policy, default_rules, make_train_step,
                           opt_state_shardings, rules_variant, _shardings_for)
from .compile_cache import enable_compile_cache


def build_mesh(spec: str):
    """'16x16' → mesh over (data, model); '2x16x16' adds the pod axis."""
    dims = tuple(int(x) for x in spec.split("x"))
    axes = ("pod", "data", "model")[-len(dims):]
    # Auto axes: the step shards through logical-axis constraints
    # (parallel.sharding), which jax.make_mesh's default Explicit axes refuse
    return jax.make_mesh(dims, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(dims))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--rules", default="default")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--state-dtype", default="float32")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_config(args.arch) if args.preset == "full"
           else get_smoke_config(args.arch))
    if cfg.is_encdec or cfg.family == "vlm":
        frontend_seq = max(cfg.frontend_seq, args.seq // 2) \
            if cfg.family == "vlm" else args.seq // 2
    else:
        frontend_seq = 0
    model = Model(cfg)
    mesh = build_mesh(args.mesh)
    if args.rules == "auto":
        chips = int(np.prod([int(x) for x in args.mesh.split("x")]))
        name = auto_policy(cfg, "train", args.global_batch, chips)
        print(f"[train] auto policy → {name}", flush=True)
        rules = rules_variant(name)
    else:
        rules = rules_variant(args.rules)

    opt = AdamW(AdamWConfig(
        lr=cosine_warmup(args.lr, args.warmup, args.steps),
        state_dtype=args.state_dtype))

    rng = jax.random.PRNGKey(args.seed)
    abstract_params = jax.eval_shape(model.init, rng)
    p_sh = _shardings_for(abstract_params, param_names(abstract_params),
                          rules, mesh)
    o_sh = opt_state_shardings(p_sh, mesh)

    ckpt = None
    start_step = 0
    if args.ckpt_dir:
        ckpt = CheckpointManager(CheckpointConfig(
            directory=args.ckpt_dir, keep=args.keep,
            save_every=args.save_every))

    with axis_rules(rules, mesh):
        if ckpt and args.resume and ckpt.latest_step() is not None:
            abstract_opt = jax.eval_shape(opt.init, abstract_params)
            state_tpl = {"params": abstract_params, "opt": abstract_opt}
            state_sh = {"params": p_sh, "opt": o_sh}
            state, start_step, extra = ckpt.restore(state_tpl, state_sh)
            params, opt_state = state["params"], state["opt"]
            print(f"[train] resumed from step {start_step} "
                  f"(loss was {extra.get('loss')})", flush=True)
        else:
            params = jax.jit(model.init, out_shardings=p_sh)(rng)
            opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)

        step_fn = make_train_step(model, opt, microbatches=args.microbatches)
        jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))

        data_cfg = DataConfig(
            vocab=cfg.vocab, seq=args.seq, global_batch=args.global_batch,
            seed=args.seed, frontend_seq=frontend_seq,
            d_model=cfg.d_model if frontend_seq else 0, encdec=cfg.is_encdec)
        pipe = Prefetcher(SyntheticLM(data_cfg), start_step, depth=2,
                          max_steps=args.steps - start_step)

        # preemption: first SIGTERM/SIGINT finishes the current step,
        # checkpoints, and exits 0 — the cluster scheduler restarts with
        # --resume and training continues bit-exactly.
        preempted = {"flag": False}

        def _handler(signum, frame):
            print(f"[train] signal {signum}: checkpoint-and-exit after this "
                  "step", flush=True)
            preempted["flag"] = True

        old_term = signal.signal(signal.SIGTERM, _handler)
        old_int = signal.signal(signal.SIGINT, _handler)

        last_loss = float("nan")
        t0 = time.time()
        step = start_step
        try:
            for batch in pipe:
                params, opt_state, metrics = jitted(params, opt_state, batch)
                step += 1
                if step % args.log_every == 0 or step == args.steps:
                    metrics = jax.device_get(metrics)
                    last_loss = float(metrics["loss"])
                    dt = (time.time() - t0) / args.log_every
                    t0 = time.time()
                    toks = args.global_batch * args.seq
                    print(f"[train] step {step:6d} loss {last_loss:.4f} "
                          f"gnorm {float(metrics['grad_norm']):.3f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"{dt:.2f}s/step {toks/dt:,.0f} tok/s", flush=True)
                if ckpt and (ckpt.should_save(step) or preempted["flag"]
                             or step == args.steps):
                    ckpt.save(step, {"params": params, "opt": opt_state},
                              extra={"loss": last_loss}, blocking=False)
                if preempted["flag"]:
                    break
        finally:
            pipe.close()
            if ckpt:
                ckpt.wait()
            signal.signal(signal.SIGTERM, old_term)
            signal.signal(signal.SIGINT, old_int)

    print(f"[train] done at step {step} (loss {last_loss:.4f})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
