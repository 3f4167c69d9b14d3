"""Train a ~100M-param LM for a few hundred steps on the deterministic token
pipeline — the end-to-end training driver over the public API (mesh, sharded
init, grad-accum train step, async checkpoints).

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse

from repro.launch import train
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()
    enable_compile_cache()
    # ~100M params: a width-512, 8-layer llama-style decoder
    import repro.configs.stablelm_3b as base
    import repro.models.lm as lm
    import dataclasses
    cfg = dataclasses.replace(
        base.config(), name="lm-100m", n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=8, d_head=64, d_ff=1536, vocab=50304, remat="none")
    import repro.configs as configs
    configs.ALIASES["lm-100m"] = "lm-100m"  # transient registration

    # drive the launcher directly with the custom config
    import jax, jax.numpy as jnp, numpy as np, time
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.train import optim
    from repro.train.step import METRICS_KEYS, TrainConfig, make_train_step
    from repro.data.tokens import TokenPipeline
    from repro.ckpt import manager as ckpt

    print(f"params: {lm.count_params(cfg)/1e6:.1f}M")
    mesh = make_mesh((1, 1), ("data", "model"))
    ms = shd.mesh_shape_dict(mesh)
    tcfg = TrainConfig(microbatches=1, adamw=optim.AdamWConfig(
        lr=3e-4, weight_decay=0.1, grad_clip=1.0))
    params, specs = lm.init(jax.random.key(0), cfg, ms)
    opt = optim.init(params, tcfg.adamw)
    step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
    pipe = TokenPipeline(0, 8, 512, cfg.vocab)
    mgr = ckpt.CheckpointManager(args.ckpt_dir, keep=2, save_interval=100)
    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        params, opt, m = step_fn(params, opt, batch)
        if step % 20 == 0:
            print(f"step {step:4d} loss {float(m['loss']):.4f} "
                  f"({(time.time()-t0)/(step+1)*1000:.0f} ms/step)")
        if mgr.should_save(step):
            mgr.save_async(step, (params, opt))
    mgr.wait()
    print(f"final loss {float(m['loss']):.4f} — done")


if __name__ == "__main__":
    main()
