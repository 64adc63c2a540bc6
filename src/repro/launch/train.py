"""Fault-tolerant training driver (runs for real at host scale; the
production-mesh path is exercised by dryrun.py).

Features (DESIGN.md §5): deterministic resumable data (batch = f(seed,
step)), async checkpointing with keep-last-k + integrity hashes, automatic
resume from the newest complete checkpoint, ELASTIC restart (a checkpoint
taken on one mesh restores onto another), straggler watchdog (step-time
EWMA; steps slower than ``straggler_factor`` x median are logged and
counted — on real fleets this feeds the rebalancer), and optional
int8-compressed cross-pod gradient sync.

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 20 \
      --reduced --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


from repro.configs import get_config, reduced
from repro.launch.mesh import make_host_mesh
from repro.launch.shapes import specs_to_shardings
from repro.models import Ctx, build
from repro.train.checkpoint import CheckpointManager, restore_checkpoint
from repro.train.data import TokenPipeline
from repro.train.optimizer import AdamW, cosine_schedule
from repro.train.train_step import make_train_step


def train(arch: str, steps: int = 20, use_reduced: bool = True,
          ckpt_dir: str = "/tmp/repro_ckpt", batch: int = 8,
          seq: int = 64, ckpt_every: int = 5, microbatch: int = 1,
          data_axis: int = 1, model_axis: int = 1, seed: int = 0,
          straggler_factor: float = 3.0, lr: float = 1e-3,
          log_every: int = 1):
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    api = build(cfg)
    mesh = make_host_mesh(data_axis, model_axis)
    opt = AdamW(lr=cosine_schedule(lr, max(steps // 10, 1), steps))
    step_fn = make_train_step(api, mesh, opt, microbatch=microbatch)

    with jax.set_mesh(mesh):
        pspecs = api.param_pspecs()
        param_sh = specs_to_shardings(pspecs, mesh)
        params = jax.device_put(api.init_params(jax.random.PRNGKey(seed)),
                                param_sh)
        opt_state = opt.init(params)

        mgr = CheckpointManager(ckpt_dir, keep_last=3)
        start = 0
        latest = mgr.latest_step()
        if latest is not None:
            state = restore_checkpoint(
                ckpt_dir, latest, {"params": params, "opt": opt_state},
                shardings={"params": param_sh,
                           "opt": jax.tree.map(lambda x: x.sharding,
                                               opt_state)})
            params, opt_state = state["params"], state["opt"]
            start = latest
            print(f"[resume] step {start} (elastic: mesh "
                  f"{data_axis}x{model_axis})", flush=True)

        pipe = TokenPipeline(cfg, batch, seq, seed=seed)
        losses, times = [], []
        for step in range(start, steps):
            b = pipe.batch_at(step)   # deterministic: resume-safe
            b = {k: jnp.asarray(v) for k, v in b.items()}
            t0 = time.time()
            params, opt_state, metrics = step_fn(params, opt_state, b)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            times.append(dt)
            losses.append(loss)
            med = float(np.median(times))
            if len(times) > 3 and dt > straggler_factor * med:
                print(f"[straggler] step {step}: {dt:.2f}s vs median "
                      f"{med:.2f}s — flagged for rebalance", flush=True)
            if step % log_every == 0:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms", flush=True)
            if (step + 1) % ckpt_every == 0 or step + 1 == steps:
                mgr.save(step + 1, {"params": params, "opt": opt_state})
        mgr.wait()
        return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--data-axis", type=int, default=1)
    ap.add_argument("--model-axis", type=int, default=1)
    args = ap.parse_args()
    losses = train(args.arch, args.steps, args.reduced, args.ckpt_dir,
                   args.batch, args.seq, microbatch=args.microbatch,
                   data_axis=args.data_axis, model_axis=args.model_axis)
    print(json.dumps({"first_loss": losses[0], "last_loss": losses[-1]}))


if __name__ == "__main__":
    main()
