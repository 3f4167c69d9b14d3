"""Where a decode tick's host round trip goes: the program's own spans,
read from the traced part of an LM cell's window, on the chip.

    python3 bench/tick_split.py --workload <name> --seconds <s> \
        --seeds 11,12,13 [--attach 1,0] [--out FILE]

Each seed is one ``--trace 1`` run of the cell, as ``bench/run.py`` makes
it, once for each ``--attach`` value.  With ``1`` one
``Tracer(clock=WallClock())`` is attached to the gateway's batcher and
adapter from ``Run.trace_begin`` to ``Run.trace_end``, so the program's
``repro:`` spans are in the trace; with ``0`` nothing is attached, which
reads what tracing costs: compare the runs' ``tick_ms.decode``.  One JSON
line per run goes to stdout and, with ``--out``, to that file: the
harness's per-layer metrics, the device's busy and window seconds, and
``program_spans.tick_split`` of the trace.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@contextlib.contextmanager
def program_tracer():
    """While open, the gateway each run builds (``spec.make_gateway``) gets
    a wall-clock tracer of its own on its batcher and adapter for the
    traced part of the window."""
    from bench import harness
    from repro.serve import spec
    from repro.serve.obs import Tracer, WallClock
    make, begin, end = (spec.make_gateway, harness.Run.trace_begin,
                        harness.Run.trace_end)
    # the run's batcher, held only until its trace ends: the run frees
    # the gateway's device memory before its reference runs
    batcher = []

    def make_gateway(*a, **kw):
        gw = make(*a, **kw)
        batcher[:] = [gw.batcher]
        return gw

    def trace_begin(r):
        begin(r)
        b = batcher[0]
        b.tracer = b.adapter.tracer = Tracer(clock=WallClock())

    def trace_end(r):
        b = batcher.pop()
        b.tracer = b.adapter.tracer = None
        end(r)

    spec.make_gateway = make_gateway
    harness.Run.trace_begin, harness.Run.trace_end = trace_begin, trace_end
    try:
        yield
    finally:
        batcher.clear()
        spec.make_gateway = make
        harness.Run.trace_begin, harness.Run.trace_end = begin, end


def split_run(workload: str, seed: int, seconds: float, attach: bool,
              **run_kw) -> dict:
    """One traced run of ``workload``; ``run_kw`` go to
    ``harness.run_cell`` (the CPU tests' smoke sizes)."""
    from bench import harness, program_spans, trace_reduce
    keep = tempfile.mkdtemp(prefix="tick-split-")
    try:
        with (program_tracer() if attach else contextlib.nullcontext()):
            line = harness.run_cell(workload, seed, seconds, True,
                                    keep_trace=keep, **run_kw)
        prog = program_spans.load(trace_reduce.find(keep))
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    dev = line["device"]
    split = program_spans.tick_split(prog)
    if "tick_idle_ms.decode" in split:
        split["idle_sum_ms"] = split["ticks"] * (
            split["tick_idle_ms.decode"] + split["loop_idle_ms.decode"])
        split["window_idle_ms"] = 1e3 * (dev["window_s"] - dev["busy_s"])
    return {"seed": seed, "attach": attach, "correct": line["correct"],
            "metrics": {k: v["value"] for k, v in line["metrics"].items()},
            "busy_s": dev["busy_s"], "window_s": dev["window_s"],
            "split": split, "breakdown": line["breakdown"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--attach", default="1,0")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        for attach in [bool(int(a)) for a in args.attach.split(",") if a]:
            t = time.perf_counter()
            row = split_run(args.workload, seed, args.seconds, attach,
                            t_start=time.perf_counter())
            row["wall_s"] = time.perf_counter() - t
            print(json.dumps(row), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
