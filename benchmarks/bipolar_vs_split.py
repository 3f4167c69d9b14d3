"""Quantify §IV.B: bipolar vs split-unipolar error near the sign activation's
decision point (the reason the paper splits weights into pos/neg banks)."""
from __future__ import annotations

from benchmarks.common import emit, timed
from repro.core import bipolar


def run(quiet: bool = False):
    for bits in (4, 6, 8):
        (pair, us) = timed(bipolar.decision_point_errors, bits, 512,
                           warmup=0, iters=1)
        err_b, err_s = pair
        emit(f"bipolar/decision_point_{bits}bit", us,
             f"bipolar_err={err_b.mean():.4f} split_err={err_s.mean():.4f} "
             f"split_advantage={err_b.mean()/max(err_s.mean(),1e-9):.2f}x")
    return True


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
