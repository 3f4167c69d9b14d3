"""End-to-end driver for the paper's system (its 'kind' is near-sensor
inference): pretrain LeNet-5 float -> swap the first layer into the
stochastic domain -> retrain the binary remainder -> report accuracy +
energy, reproducing the hybrid pipeline of Fig. 3.

Run:  PYTHONPATH=src python examples/near_sensor_lenet.py [--bits 4]
      [--steps 400] [--full-lenet]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import energy, hybrid
from repro.core.sc_layer import SCConfig
from repro.data import mnist_synth
from repro.models import lenet
from repro.train import optim
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--steps", type=int, default=400)
    ap.add_argument("--retrain-steps", type=int, default=250)
    ap.add_argument("--full-lenet", action="store_true",
                    help="paper-size LeNet (32/64 filters); default reduced")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (lenet.LeNetConfig() if args.full_lenet
           else lenet.LeNetConfig(conv1_filters=16, conv2_filters=32,
                                  dense=128))
    xtr, ytr, xte, yte = mnist_synth.dataset(6000, 1500)
    print(f"LeNet-5 ({cfg.conv1_filters}/{cfg.conv2_filters} filters), "
          f"synthetic digit set {len(xtr)}/{len(xte)} (offline MNIST stand-in)")

    # -- stage 1: float pretraining (paper: TF/Keras; here pure JAX) --------
    params = lenet.init(jax.random.key(0), cfg)
    opt_cfg = optim.AdamWConfig(lr=1e-3)
    opt = optim.init(params, opt_cfg)
    key = jax.random.key(1)
    t0 = time.time()
    for step, (xb, yb) in enumerate(
            mnist_synth.batches(xtr, ytr, 64, 0, args.steps)):
        key, sub = jax.random.split(key)
        params, opt, loss = hybrid.float_train_step(
            params, opt, jnp.asarray(xb), jnp.asarray(yb), sub, cfg, opt_cfg)
        if step % 100 == 0:
            print(f"  pretrain step {step:4d} loss {float(loss):.3f}")
    acc_float = hybrid.evaluate(params, xte, yte, cfg,
                                hybrid.HybridConfig(mode="float"))
    print(f"float baseline: {100*(1-acc_float):.2f}% misclassification "
          f"({time.time()-t0:.0f}s)")

    # -- stage 2: swap first layer into the stochastic domain ---------------
    hcfg = hybrid.HybridConfig(mode="sc",
                               sc=SCConfig(bits=args.bits, adder="tff"))
    feats_tr = hybrid.cache_first_layer(params, xtr, hcfg)
    feats_te = hybrid.cache_first_layer(params, xte, hcfg)
    acc_before = hybrid.evaluate_cached(params, feats_te, yte, cfg)
    print(f"hybrid @{args.bits}-bit BEFORE retraining: "
          f"{100*(1-acc_before):.2f}%")

    # -- stage 3: retrain the binary remainder ------------------------------
    params_rt = hybrid.retrain_tail(params, feats_tr, ytr, cfg,
                                    steps=args.retrain_steps, batch=128)
    acc_after = hybrid.evaluate_cached(params_rt, feats_te, yte, cfg)
    print(f"hybrid @{args.bits}-bit AFTER retraining:  "
          f"{100*(1-acc_after):.2f}%  "
          f"(float {100*(1-acc_float):.2f}%)")

    # -- energy story --------------------------------------------------------
    r = energy.report(args.bits)
    print(f"energy @{args.bits}-bit: SC {r.sc_energy_nj:.2f} nJ/frame vs "
          f"binary {r.bin_energy_nj:.2f} -> {r.efficiency_gain:.1f}x saving")


if __name__ == "__main__":
    main()
