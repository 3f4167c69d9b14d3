"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window with the profiler on for part of it and reports the cell's
per-layer metrics, the device's busy and window seconds and a breakdown.
Either way the outputs of the window are compared with the plain
reference.  The last stdout line is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, [``breakdown``],
``checks``); the last stderr lines are the compared numbers beside their
limits.  With no TPU, or fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache lives at a fixed path in this
# checkout, whatever the environment names: only the first run of a cell
# here compiles, and two checkouts never share programs.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler trace into this directory")
    args = ap.parse_args(argv)
    from bench import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), keep_trace=args.keep_trace,
                                t_start=T_START)
    except harness.BenchError as e:
        harness.log(f"error: {e}")
        return 2
    harness.report(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
