"""Readings that set a cell's correctness limit: the program's compared
number over many seeds, and the control's, in one process on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds 11,12,13 [--control-seeds 11,12,13] [--out FILE]

Each seed is one whole run of the cell (set-up, window, reference), as
``bench/run.py --trace 0`` makes it.  On each control seed the cell runs
once more for each of its reference's ``CONTROLS``: the control, the
reference one precision step below the configuration's, takes the
program's place in the comparison, so its readings are the line's
``checks`` and ``correct`` must be false; the program's own readings of
that run are in ``info``.  One JSON line per run goes to stdout and,
with ``--out``, to that file.  The benchmark's own runs never compute a
control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from bench import harness
    bench = harness.benchmark()
    cfg = harness.config(bench, harness.cell(bench, args.workload)["config"])
    controls = harness.module("reference", cfg["reference"]).CONTROLS
    runs = [(int(s), None) for s in args.seeds.split(",") if s]
    runs += [(int(s), c) for s in args.control_seeds.split(",") if s
             for c in controls]
    for seed, control in runs:
        t = time.perf_counter()
        line = harness.run_cell(args.workload, seed, args.seconds, False,
                                control=control,
                                t_start=time.perf_counter())
        row = {"seed": seed, "control": control, "correct": line["correct"],
               "checks": line["checks"], "info": line["info"],
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "memory_peak_bytes": line["device"]["memory_peak_bytes"],
               "wall_s": time.perf_counter() - t}
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
