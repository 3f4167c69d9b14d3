"""Paper Table 2: MUX-adder configurations vs the new TFF adder."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.core import arith, bitstream as bs, sng

PAPER = {  # config -> (8-bit, 4-bit)
    "old_random_lfsr": (3.24e-4, 5.55e-3),
    "old_random_tff": (5.49e-4, 5.49e-3),
    "old_lfsr_tff": (1.06e-4, 2.66e-3),
    "new_tff": (1.91e-6, 4.88e-4),
}


def _random_streams(bits, R, seed):
    N = 1 << bits
    rng = np.random.default_rng(seed)
    a = np.arange(N)
    return bs.pack_bits(jnp.asarray(rng.random((R, N, N)) < (a[:, None] / N)))


def old_adder_mse(bits: int, config: str, R: int = 8) -> float:
    N = 1 << bits
    a = np.arange(N)
    exact = (a[:, None] + a[None, :]) / (2 * N)
    if config == "old_lfsr_tff":
        # deterministic LFSR data streams + toggling select
        ca = sng.lfsr_sequence(bits, which=0, seed=9)
        cb = sng.lfsr_sequence(bits, which=1, seed=9)
        SA = sng.generate(jnp.arange(N), ca, N)[None]
        SB = sng.generate(jnp.arange(N), cb, N)[None]
        sel = arith.tff_select_stream(N)
    else:
        SA = _random_streams(bits, R, 0)
        SB = _random_streams(bits, R, 1)
        if config == "old_random_lfsr":
            sel = sng.generate(jnp.asarray(N // 2), sng.lfsr_sequence(bits), N)
        else:  # old_random_tff
            sel = arith.tff_select_stream(N)
    z = arith.mux_add(SA[:, :, None], SB[:, None, :], sel)
    cz = np.asarray(bs.popcount(z), np.float64)
    return float(((cz / N - exact[None]) ** 2).mean())


def new_adder_mse(bits: int) -> float:
    """Exhaustive; equals 1/(8N^2) analytically (tests prove it)."""
    N = 1 << bits
    a = jnp.arange(N)
    cz = arith.tff_add_count(a[:, None], a[None, :], 0)
    exact = (np.arange(N)[:, None] + np.arange(N)[None, :]) / (2 * N)
    return float(((np.asarray(cz, np.float64) / N - exact) ** 2).mean())


def run(quiet: bool = False):
    rows = {}
    for cfgname in ("old_random_lfsr", "old_random_tff", "old_lfsr_tff"):
        (m8, us) = timed(old_adder_mse, 8, cfgname, warmup=0, iters=1)
        m4 = old_adder_mse(4, cfgname)
        rows[cfgname] = (m8, m4)
        p8, p4 = PAPER[cfgname]
        emit(f"table2/{cfgname}", us,
             f"mse8={m8:.3e} (paper {p8:.2e}) mse4={m4:.3e} (paper {p4:.2e})")
    (n8, us) = timed(new_adder_mse, 8, warmup=0, iters=1)
    n4 = new_adder_mse(4)
    rows["new_tff"] = (n8, n4)
    emit("table2/new_tff", us,
         f"mse8={n8:.3e} (paper 1.91e-06 EXACT) mse4={n4:.3e} "
         f"(paper 4.88e-04 EXACT)")
    gain = rows["old_random_lfsr"][0] / n8
    emit("table2/new_vs_old_gain", 0.0, f"8bit_mse_improvement={gain:.0f}x")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
