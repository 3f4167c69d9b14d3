"""Paper Table 1: stochastic multiplier MSE per SNG scheme (exhaustive)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.core import arith, bitstream as bs, sng

PAPER = {  # scheme -> (8-bit, 4-bit)
    "lfsr_shared": (2.78e-3, 2.99e-3),
    "lfsr_pair": (2.57e-4, 1.60e-3),
    "lowdisc": (1.28e-5, 1.01e-3),
    "ramp_lowdisc": (8.66e-6, 7.21e-4),
}


def multiplier_mse(scheme: str, bits: int) -> float:
    """Exhaustive over all (a, b) input pairs, as in the paper."""
    N = 1 << bits
    ca, cb = sng.codes_for_scheme(scheme, bits)
    a = jnp.arange(N)
    SA = sng.generate(a, ca, N)
    SB = sng.generate(a, cb, N)
    prod = np.asarray(bs.popcount(arith.mult(SA[:, None], SB[None])),
                      np.float64)
    av = np.arange(N)[:, None] / N
    bv = np.arange(N)[None, :] / N
    return float(((prod / N - av * bv) ** 2).mean())


def run(quiet: bool = False):
    rows = {}
    for scheme in sng.SCHEMES:
        (m8, us8) = timed(multiplier_mse, scheme, 8, warmup=0, iters=1)
        m4 = multiplier_mse(scheme, 4)
        rows[scheme] = (m8, m4)
        p8, p4 = PAPER[scheme]
        emit(f"table1/{scheme}", us8,
             f"mse8={m8:.3e} (paper {p8:.2e}) mse4={m4:.3e} (paper {p4:.2e})")
    order8 = [rows[s][0] for s in sng.SCHEMES]
    ok = all(a > b for a, b in zip(order8, order8[1:]))
    emit("table1/ordering", 0.0, f"paper_ordering_reproduced={ok}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
