"""Training launcher: mesh setup, sharded init, checkpoint/restart loop.

Fault-tolerance contract (designed for 1000+ nodes, exercised here on the
local device set):
  - RESTART: on launch, the latest intact checkpoint (atomic dirs + CRC) is
    restored and the data pipeline resumes from the recorded step — re-run
    the same command after killing the process and training continues.
  - ELASTIC: pass a different --mesh and the same checkpoint re-shards onto
    the new topology (specs are functions of the mesh, see dist.sharding).
  - STRAGGLERS / LOST HOSTS: batches are a stateless (seed, step) map, so a
    respawned host recomputes its shard without coordination.  On a real
    multi-controller deployment the runner wraps this loop with a step
    barrier + timeout + respawn (the checkpoint/restore path here is exactly
    what that respawn executes).
  - ASYNC CHECKPOINTS: device->host snapshot is synchronous, file I/O
    overlaps the next steps (CheckpointManager.save_async).

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --smoke \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.ckpt import manager as ckpt
from repro.data.tokens import TokenPipeline
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.train import optim
from repro.train.step import METRICS_KEYS, TrainConfig, make_train_step


def parse_mesh(spec: str):
    """"1" | "2x2" | "2x4 data,model" style."""
    if " " in spec:
        dims, names = spec.split(" ")
        shape = tuple(int(x) for x in dims.split("x"))
        axes = tuple(names.split(","))
    else:
        shape = tuple(int(x) for x in spec.split("x"))
        axes = ("data", "model")[:len(shape)] if len(shape) <= 2 else \
               ("pod", "data", "model")
    return make_mesh(shape, axes)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.smoke_config(args.arch) if args.smoke
           else configs.config(args.arch))
    mesh = parse_mesh(args.mesh)
    mesh_shape = shd.mesh_shape_dict(mesh)
    print(f"arch={cfg.name} params~{lm.count_params(cfg)/1e6:.1f}M "
          f"mesh={mesh_shape}")

    tcfg = TrainConfig(
        microbatches=args.microbatches,
        adamw=optim.AdamWConfig(lr=args.lr, weight_decay=0.1, grad_clip=1.0,
                                master_dtype=jnp.float32))
    with shd.use_activation_mesh(mesh):
        params, specs = lm.init(jax.random.key(args.seed), cfg, mesh_shape)
        params = jax.device_put(params, shd.named(mesh, specs))
        opt_state = optim.init(params, tcfg.adamw)
        opt_specs = shd.opt_state_specs(specs, params, mesh_shape)
        opt_state = jax.device_put(opt_state, shd.named(mesh, opt_specs))

        step_fn = make_train_step(cfg, tcfg)
        bspec = P(shd.batch_spec_axis(mesh_shape, args.batch), None)
        train_step = jax.jit(
            step_fn,
            in_shardings=(shd.named(mesh, specs), shd.named(mesh, opt_specs),
                          {"tokens": shd.named(mesh, bspec),
                           "labels": shd.named(mesh, bspec)}),
            out_shardings=(shd.named(mesh, specs),
                           shd.named(mesh, opt_specs),
                           {k: shd.named(mesh, P()) for k in METRICS_KEYS}),
            donate_argnums=(0, 1))

        start_step = 0
        mgr = None
        if args.ckpt_dir:
            mgr = ckpt.CheckpointManager(args.ckpt_dir, keep=3,
                                         save_interval=args.ckpt_every)
            if ckpt.latest_step(args.ckpt_dir) is not None:
                (params, opt_state), manifest = mgr.restore_latest(
                    (params, opt_state),
                    shardings=(shd.named(mesh, specs),
                               shd.named(mesh, opt_specs)))
                start_step = manifest["step"]
                print(f"resumed from step {start_step}")

        pipe = TokenPipeline(args.seed, args.batch, args.seq, cfg.vocab,
                             start_step=start_step)
        t0 = time.time()
        for step in range(start_step, args.steps):
            batch = jax.tree.map(jnp.asarray, pipe.next())
            params, opt_state, metrics = train_step(params, opt_state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                dt = (time.time() - t0) / max(1, step - start_step + 1)
                print(f"step {step:5d} loss {loss:.4f} "
                      f"({dt*1000:.0f} ms/step)")
                assert np.isfinite(loss), "loss diverged"
            if mgr and mgr.should_save(step):
                mgr.save_async(step + 1, (params, opt_state),
                               extra={"arch": cfg.name})
        if mgr:
            mgr.save_sync(args.steps, (params, opt_state),
                          extra={"arch": cfg.name})
            mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
