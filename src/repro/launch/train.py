"""Training launcher.

Production path: ``--mesh pod256|pod512`` builds the production mesh and
expects real TPU devices (on this CPU container use ``--smoke``, which runs
a reduced config on a 1-device mesh and actually trains).

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import checkpoint
from repro.configs.base import get_config
from repro.data.pipeline import DataConfig, SyntheticLM, make_batch
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.sharding import tree_shardings, use_mesh
from repro.train import optimizer as opt
from repro.train.train_step import make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the local device")
    ap.add_argument("--mesh", default="cpu", choices=["cpu", "pod256",
                                                      "pod512"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.mesh == "cpu":
        mesh = mesh_mod.make_cpu_mesh()
    else:
        mesh = mesh_mod.make_production_mesh(
            multi_pod=(args.mesh == "pod512"))

    ocfg = opt.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                           total_steps=args.steps)
    with use_mesh(mesh):
        key = jax.random.PRNGKey(0)
        params = M.init_params(cfg, key)
        ostate = opt.init_adamw(ocfg, params)
        step_fn = jax.jit(make_train_step(cfg, ocfg, remat=False))

        from repro.configs.base import InputShape
        shape = InputShape("cli", args.seq, args.batch, "train")
        start = 0
        if args.ckpt_dir and checkpoint.latest_step(args.ckpt_dir) is not None:
            (params, ostate), start = checkpoint.restore(
                args.ckpt_dir, (params, ostate))
            print(f"restored step {start}")

        losses = []
        t0 = time.time()
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in make_batch(cfg, shape, step).items()}
            params, ostate, metrics = step_fn(params, ostate, batch)
            losses.append(float(metrics["loss"]))
            if step % args.log_every == 0 or step == args.steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"({dt / max(step - start + 1, 1):.2f} s/step)")
            if (args.ckpt_dir and args.ckpt_every
                    and (step + 1) % args.ckpt_every == 0):
                checkpoint.save(args.ckpt_dir, step + 1, (params, ostate))
        print(f"final loss {losses[-1]:.4f} "
              f"(start {losses[0]:.4f}, drop {losses[0] - losses[-1]:.4f})")
        return losses


if __name__ == "__main__":
    main()
