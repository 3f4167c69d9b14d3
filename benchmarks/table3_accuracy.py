"""Paper Table 3 (misclassification block): binary vs old-SC vs new-SC hybrid
designs across precisions, with binary-tail retraining.

Offline note: runs on the procedural synthetic digit set (MNIST stand-in) —
absolute accuracies differ from the paper's MNIST numbers; the validated
claims are relative (see EXPERIMENTS.md): retraining recovers the hybrid to
within a small gap of the binary design at >=4 bits, the new adder beats the
old SC design, and 2-bit collapses.

Fast mode (default, used by benchmarks.run): bits {2,4,8}, reduced data.
Full mode (--full): bits 2..8, more data/steps, old-SC at 8-bit included.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.core import hybrid
from repro.core.sc_layer import SCConfig
from repro.data import mnist_synth
from repro.models import lenet
from repro.train import optim

PAPER_MISCLASS = {  # bits: (binary, old_sc, this_work) %
    8: (0.89, 2.22, 0.94), 7: (0.86, 3.91, 0.99), 6: (0.89, 1.30, 1.04),
    5: (0.74, 1.55, 1.12), 4: (0.79, 1.63, 1.04), 3: (0.79, 2.71, 2.20),
    2: (1.30, 4.89, 43.82),
}


@functools.lru_cache(maxsize=2)
def _pretrained(n_train: int, n_test: int, steps: int):
    cfg = lenet.LeNetConfig()
    xtr, ytr, xte, yte = mnist_synth.dataset(n_train, n_test)
    params = lenet.init(jax.random.key(0), cfg)
    opt_cfg = optim.AdamWConfig(lr=1e-3)
    opt = optim.init(params, opt_cfg)
    key = jax.random.key(1)
    for xb, yb in mnist_synth.batches(xtr, ytr, 64, 0, steps):
        key, sub = jax.random.split(key)
        params, opt, _ = hybrid.float_train_step(
            params, opt, jnp.asarray(xb), jnp.asarray(yb), sub, cfg, opt_cfg)
    return cfg, params, (xtr, ytr, xte, yte)


def eval_design(cfg, params, data, hcfg, retrain_steps, n_retrain):
    xtr, ytr, xte, yte = data
    feats_tr = hybrid.cache_first_layer(params, xtr[:n_retrain], hcfg)
    feats_te = hybrid.cache_first_layer(params, xte, hcfg)
    p2 = hybrid.retrain_tail(params, feats_tr, ytr[:n_retrain], cfg,
                             steps=retrain_steps, batch=128)
    return 1.0 - hybrid.evaluate_cached(p2, feats_te, yte, cfg)


def run(full: bool = False):
    n_train, n_test, steps = (8000, 2000, 600) if full else (3000, 800, 250)
    retrain_steps, n_retrain = (400, 6000) if full else (150, 2500)
    bits_list = list(range(2, 9)) if full else [2, 4, 8]
    (out, us) = timed(_pretrained, n_train, n_test, steps, warmup=0, iters=1)
    cfg, params, data = out
    float_acc = hybrid.evaluate(params, data[2], data[3], cfg,
                                hybrid.HybridConfig(mode="float"))
    emit("table3_acc/float_baseline", us,
         f"misclass={100*(1-float_acc):.2f}%")

    results = {}
    for bits in bits_list:
        row = {}
        (row["binary"], us_b) = timed(
            eval_design, cfg, params, data,
            hybrid.HybridConfig(mode="binary", bits=bits),
            retrain_steps, n_retrain, warmup=0, iters=1)
        (row["new_sc"], us_n) = timed(
            eval_design, cfg, params, data,
            hybrid.HybridConfig(mode="sc", sc=SCConfig(bits=bits,
                                                       adder="tff")),
            retrain_steps, n_retrain, warmup=0, iters=1)
        # old SC (LFSR-pair SNGs + MUX tree) only at stream level — heavier;
        # run at <=4 bits in fast mode
        if full or bits <= 4:
            (row["old_sc"], us_o) = timed(
                eval_design, cfg, params, data,
                hybrid.HybridConfig(
                    mode="sc",
                    sc=SCConfig(bits=bits, scheme="lfsr_pair", adder="mux"),
                    sc_impl="streams"),
                retrain_steps, n_retrain, warmup=0, iters=1)
        results[bits] = row
        pb, po, pn = PAPER_MISCLASS[bits]
        emit(f"table3_acc/{bits}bit", us_b + us_n,
             " ".join(f"{k}={100*v:.2f}%" for k, v in row.items())
             + f" | paper: bin={pb}% old={po}% new={pn}%")

    # relative claims
    b4 = results.get(4, {})
    if "binary" in b4 and "new_sc" in b4:
        gap4 = (b4["new_sc"] - b4["binary"]) * 100
        emit("table3_acc/claim_gap_4bit", 0.0,
             f"hybrid_minus_binary={gap4:+.2f}pp (paper +0.25pp)")
    if "old_sc" in b4:
        emit("table3_acc/claim_new_beats_old_4bit", 0.0,
             f"old-new={100*(b4['old_sc']-b4['new_sc']):+.2f}pp (paper +0.59pp)")
    if 2 in results and 4 in results:
        emit("table3_acc/claim_2bit_collapse", 0.0,
             f"err2={100*results[2]['new_sc']:.1f}% >> "
             f"err4={100*results[4]['new_sc']:.1f}% "
             f"(paper 43.82% vs 1.04%)")
    return results


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    run(full=ap.parse_args().full)
