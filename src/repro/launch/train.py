"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Runs the fault-tolerant driver (checkpoint/restart, heartbeats, straggler
monitor, deterministic data) on whatever devices exist — reduced configs
on one CPU device for local runs, or the production mesh on a real
cluster (--mesh data,model).
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="use the reduced (CPU-sized) config")
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (demo)")
    ap.add_argument("--mesh", default=None,
                    help="data,model mesh shape, e.g. 4,2")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs import get
    from repro.configs.shapes import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.models import ShardingCtx, build, from_mesh
    from repro.runtime import DriverConfig, StragglerMonitor, run
    from repro.train import (
        AdamW, SyntheticLM, cosine_schedule, init_state, make_train_step,
        state_shardings,
    )

    cfg = get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    print(f"arch={cfg.name} params={model.param_count():,}")

    if args.mesh:
        d, m = (int(v) for v in args.mesh.split(","))
        mesh = make_mesh((d, m), ("data", "model"))
        ctx = from_mesh(mesh)
    else:
        ctx = ShardingCtx()

    opt = AdamW(learning_rate=cosine_schedule(args.lr, warmup=10,
                                              total=args.steps))
    state = init_state(model, jax.random.PRNGKey(args.seed), opt,
                       compress=args.compress_grads)
    st_sh = state_shardings(model, ctx, compress=args.compress_grads)
    step_fn = jax.jit(make_train_step(model, opt, ctx,
                                      num_microbatches=args.microbatches,
                                      compress=args.compress_grads),
                      in_shardings=(st_sh, None) if ctx.enabled else None,
                      out_shardings=(st_sh, None) if ctx.enabled else None)

    shape = ShapeSpec("cli", args.seq_len, args.global_batch, "train")
    src = SyntheticLM(cfg, shape)
    mon = StragglerMonitor()

    import time
    t_last = [time.perf_counter()]

    def on_step(step, metrics):
        now = time.perf_counter()
        mon.observe(step, now - t_last[0])
        t_last[0] = now
        if step % 10 == 0 or step < 3:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f}")

    dcfg = DriverConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=args.ckpt_dir,
        heartbeat_path=os.path.join(args.ckpt_dir, "heartbeat"),
        fail_at_steps=tuple(args.fail_at))
    os.makedirs(args.ckpt_dir, exist_ok=True)
    report = run(step_fn, state, lambda s: src.place(src.batch_for_step(s),
                                                     ctx),
                 dcfg, state_shardings=st_sh, on_step=on_step)
    print(f"done: steps={report.steps_run} restarts={report.restarts} "
          f"loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f} "
          f"straggler_events={len(mon.events)}")


if __name__ == "__main__":
    main()
