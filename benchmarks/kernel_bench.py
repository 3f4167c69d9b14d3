"""sc_dot Pallas kernel: structural roofline + interpret-mode validation
timing.

No TPU in this container, so wall-clock here is the interpret-mode Python
evaluator (meaningless for TPU perf).  What IS meaningful — and reported —
is the structural analysis per BlockSpec tile: VMEM working set, bytes moved
per tile, op counts, and the derived arithmetic intensity of the packed
AND+popcount dot product (the quantity that decides compute- vs HBM-bound on
the v5e roofline).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, timed


def tile_analysis(bm: int, bo: int, K: int, bits: int):
    Wd = (1 << bits) // 32
    in_bytes = bm * K * Wd * 4 + K * bo * Wd * 4
    out_bytes = bm * bo * 4
    vmem = in_bytes + bm * K * bo * 4 + out_bytes   # + counts scratch
    # word-ops: AND + popcount-add per (m, o, k, word); tree adds per (m,o,K)
    word_ops = bm * bo * K * Wd * 2 + bm * bo * K
    intensity = word_ops / (in_bytes + out_bytes)
    return {"vmem_bytes": vmem, "hbm_bytes": in_bytes + out_bytes,
            "word_ops": word_ops, "intensity": intensity}


def layer_traffic(M: int, O: int, K: int, bits: int, bm: int, bo: int,
                  fused_posneg: bool):
    """Whole-layer HBM bytes for the pos/neg split design.

    Separate calls re-read X tiles once per weight bank AND per o-block;
    the fused variant packs both banks on the O axis.
    """
    Wd = (1 << bits) // 32
    O_eff = 2 * O if fused_posneg else O
    n_ob = -(-O_eff // bo)
    x_reads = (-(-M // bm)) * n_ob * (bm * K * Wd * 4)
    w_reads = (-(-M // bm)) * n_ob * (K * min(bo, O_eff) * Wd * 4)
    out = M * O_eff * 4
    total = x_reads + w_reads + out
    if not fused_posneg:
        total *= 2        # pos bank + neg bank as separate kernel calls
    return total


def run(quiet: bool = False):
    # paper's engine: 784 windows x 32 kernels (x2 pos/neg), K=25->32
    for bits in (5, 8):
        for bm, bo in ((128, 64), (256, 64), (512, 64)):
            a = tile_analysis(bm, bo, 32, bits)
            emit(f"kernel/sc_dot_tile_b{bits}_{bm}x{bo}", 0.0,
                 f"vmem={a['vmem_bytes']/2**20:.2f}MiB "
                 f"intensity={a['intensity']:.1f}ops/B "
                 f"fits_vmem={a['vmem_bytes'] < 16*2**20}")
    # fused pos/neg vs separate calls: whole-layer traffic (LeNet shapes)
    for bits in (5, 8):
        sep = layer_traffic(784, 32, 32, bits, 256, 64, fused_posneg=False)
        fus = layer_traffic(784, 32, 32, bits, 256, 64, fused_posneg=True)
        emit(f"kernel/posneg_fusion_b{bits}", 0.0,
             f"separate={sep/2**20:.2f}MiB fused={fus/2**20:.2f}MiB "
             f"saving={100*(1-fus/sep):.0f}%")
    # interpret-mode correctness + (non-TPU) timing of one LeNet-layer call
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    bits = 5
    Wd = (1 << bits) // 32
    x = jnp.asarray(rng.integers(0, 2**32, (784, 32, Wd), dtype=np.uint32))
    w = jnp.asarray(rng.integers(0, 2**32, (32, 64, Wd), dtype=np.uint32))
    out, us = timed(lambda: np.asarray(ops.sc_dot(x, w)), warmup=1, iters=3)
    want = np.asarray(ref.sc_dot(x, w))
    emit("kernel/sc_dot_lenet_layer", us,
         f"interpret_mode exact_match={bool((out == want).all())} "
         f"shape=784x64 (one image, 32k dot-products/s-equiv)")
    return True


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
