"""End-to-end training driver: LM train steps orchestrated as a WUKONG
workflow with fault-injected retries and periodic async checkpoints.

The inner step is jitted JAX (loss -> grads -> AdamW); the *cluster
workflow* (data shard -> step -> metrics, checkpoint fan-outs) runs on
the paper's decentralized DAG engine, which supplies Lambda-style retry
and straggler handling (DESIGN.md §2).

Defaults are laptop-sized. For the assignment's "~100M model for a few
hundred steps" run:
    PYTHONPATH=src python examples/train_lm.py --arch smollm_360m \
        --layers 8 --steps 200 --batch 8 --seq 256
(smollm_360m at 8 layers ≈ 100M params with its 49k vocab.)
"""
import argparse
import dataclasses
import os
import time

import jax

from repro.configs import get_config, reduced
from repro.core import EngineConfig, FaultConfig
from repro.models import model as M
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import checkpoint as ckpt
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.orchestrator import (
    build_training_workflow,
    run_training_workflow,
)
from repro.runtime.train import build_train_step, synthetic_batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full-width", action="store_true",
                    help="keep the arch's real width (default: reduced)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--fail-prob", type=float, default=0.02,
                    help="injected Lambda failure probability")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_width:
        cfg = reduced(cfg)
    cfg = dataclasses.replace(cfg, n_layers=args.layers
                              * cfg.pattern_period)
    n_params = sum(x.size for x in jax.tree.leaves(
        M.abstract_params(cfg)))
    print(f"arch={cfg.name} layers={cfg.n_layers} params={n_params/1e6:.1f}M "
          f"steps={args.steps} batch={args.batch}x{args.seq}")

    params, _ = M.init_model(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    jstep = jax.jit(build_train_step(cfg, AdamWConfig(lr=args.lr)))

    os.makedirs(args.ckpt_dir, exist_ok=True)
    ckpt_path = os.path.join(args.ckpt_dir, f"{cfg.name}.npz")

    def init_fn():
        # elastic resume: pick up the latest checkpoint if one exists
        if os.path.exists(ckpt_path):
            like = jax.eval_shape(lambda: {"params": params, "opt": opt})
            state, step0 = ckpt.restore(ckpt_path, like)
            print(f"resumed from checkpoint @ step {step0}")
            return (state["params"], state["opt"])
        return (params, opt)

    losses = []

    def step_fn(state, i):
        p, o = state
        batch = synthetic_batch(cfg, args.batch, args.seq, seed=i)
        p, o, m = jstep(p, o, batch)
        loss = float(m["loss"])
        losses.append((i, loss))
        return (p, o), {"loss": loss}

    def checkpoint_fn(state, i):
        p, o = state
        ckpt.save(ckpt_path, {"params": p, "opt": o}, step=i, async_=True)
        return f"ckpt@{i}"

    dag, final_key, metric_keys = build_training_workflow(
        n_steps=args.steps, step_fn=step_fn, init_fn=init_fn,
        checkpoint_fn=checkpoint_fn, checkpoint_every=args.ckpt_every)

    t0 = time.time()
    res = run_training_workflow(
        dag, final_key, metric_keys,
        EngineConfig(faults=FaultConfig(task_failure_prob=args.fail_prob,
                                        max_retries=2, seed=1),
                     job_timeout_s=24 * 3600.0))
    dt = time.time() - t0

    losses.sort()
    shown = {i: l for i, l in losses}
    first, last = losses[0][1], losses[-1][1]
    print(f"\n{args.steps} steps in {dt:.1f}s "
          f"({args.steps * args.batch * args.seq / dt:.0f} tok/s)")
    for i in sorted(shown)[:: max(1, args.steps // 10)]:
        print(f"  step {i:4d}  loss {shown[i]:.4f}")
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    print(f"checkpoint: {ckpt_path} (step {ckpt.latest_step(ckpt_path)})")


if __name__ == "__main__":
    main()
