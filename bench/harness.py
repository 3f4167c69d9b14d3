"""Runs one cell of ``BENCHMARK.json`` once and builds its result line.

Everything a cell is made of is found by name, so a new configuration,
traffic mix or per-layer metric is a new file and never an edit:

- ``BENCHMARK.json`` names the cell's configuration and traffic mix;
- the configuration is ``bench/configs/<config>.json``; its ``driver``
  names ``bench/drivers/<driver>.py`` (one per front door of the system)
  and its ``reference`` names ``bench/reference/<reference>.py``;
- the mix is ``bench/traffic/<traffic>.json``, read by ``bench/traffic.py``;
- a per-layer metric ``<name>`` is read by ``bench/metrics/<name>.py``, or,
  where that file does not exist, by the reader of its stem (the part
  before the first dot): ``device_idle_share.frames`` is read by
  ``bench/metrics/device_idle_share.py``.

A driver's ``run(r)`` sets the cell up, calls ``r.setup_done()``, drives
the window (calling ``r.trace_begin()``/``r.trace_end()`` around the
traced part when ``r.trace``), reads ``r.memory_peak()``, frees the
program's state and runs the reference.  It returns a dict with
``attempted``, ``failed``, ``e2e`` (end-to-end metric values),
``counters`` (what the per-layer readers read) and ``checks``
(``(name, value, limit)`` triples; ``correct`` is every value within its
limit).
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The cell cannot be run as asked (no chip, unknown name, ...)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((ROOT / c["file"]).read_text())
    raise BenchError(f"no configuration {name!r} in BENCHMARK.json")


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module; an unknown name fails."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The reader of a per-layer metric: its own file, else its stem's."""
    if (BENCH / "metrics" / f"{metric}.py").is_file():
        return module("metrics", metric)
    return module("metrics", metric.split(".", 1)[0])


def metrics_of(bench: dict, workload: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``workload`` reports."""
    out = []
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench[section]:
        cells = m.get("workloads")
        if cells is None and section == "per_layer":
            cells = e2e[m["moves"]].get("workloads")
        if cells is None or workload in cells:
            out.append(m)
    return out


def device_check(chips: int) -> dict:
    """The device JAX reports; refuse anything but enough TPU chips that
    run Pallas kernels through Mosaic (never the interpreter)."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
    env = os.environ.get("REPRO_KERNELS_INTERPRET", "").strip().lower()
    if env and env not in ("0", "false", "no"):
        raise BenchError("REPRO_KERNELS_INTERPRET is set: the kernels "
                         "would run in the interpreter")
    if len(devs) < chips:
        raise BenchError(f"need {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


class CompileCounter:
    """Counts programs lowered (a jit cache miss, whether the persistent
    cache then has it or not) and programs the backend compiled."""

    def __init__(self):
        import jax
        self.lowered = 0
        self.compiled = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


class Run:
    """What a driver gets: the cell's files, the run's arguments, and the
    hooks that mark set-up, the traced window and the memory peak."""

    def __init__(self, workload, cfg, mix, *, seed, seconds, trace, control,
                 t_start, compiles):
        self.workload = workload
        self.config = cfg
        self.mix = mix
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = control
        self.t_start = t_start
        self.compiles = compiles
        self.setup_s = None
        self.trace_dir = None
        self._window = None
        self.log = log

    def reference(self):
        return module("reference", self.config["reference"])

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - self.t_start
        log(f"setup_s {self.setup_s:.3f} (compiles so far: "
            f"{self.compiles.lowered} lowered, {self.compiles.compiled} "
            f"compiled by the backend)")
        return self.setup_s

    def trace_begin(self) -> None:
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.trace_dir)
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()

    def trace_end(self) -> None:
        import jax
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        import jax
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control: str | None = None, on_chip: bool = True,
             config_override=None, mix_override=None, keep_trace=None,
             t_start: float | None = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``control`` names one of the cell's reference's ``CONTROLS``, which
    then takes the program's place in the comparison (``correct`` must
    come out false); the benchmark's own runs never set it.
    ``on_chip=False`` skips the look for a chip (the CPU tests); the
    overrides replace top-level groups of the configuration or mix files
    (the tests' smoke sizes)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = benchmark()
    w = cell(bench, workload)
    device = device_check(w["chips"]) if on_chip else None
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache {enable_compile_cache()}")
    if device is None:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind, "count": 1}
    cfg = config(bench, w["config"])
    cfg.update(config_override or {})
    from bench import traffic
    mix = traffic.load(w["traffic"])
    mix.update(mix_override or {})
    driver = module("drivers", cfg["driver"])
    r = Run(workload, cfg, mix, seed=seed, seconds=seconds, trace=trace,
            control=control, t_start=t_start, compiles=CompileCounter())
    out = driver.run(r)

    metrics = {}
    if not trace:
        for m in metrics_of(bench, workload, "end_to_end"):
            value = r.setup_s if m["name"] == "setup_s" else \
                out["e2e"].get(m["name"])
            if value is None:
                raise BenchError(f"the driver measured no {m['name']}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if trace:
        from bench import costs, trace_reduce
        tr = trace_reduce.load(trace_reduce.find(r.trace_dir))
        ctx = {"counters": out["counters"], "trace": tr, "config": cfg,
               "peak": costs.peak(device["kind"]) if on_chip else None}
        for m in metrics_of(bench, workload, "per_layer"):
            value = reader(m["name"]).read(dict(ctx, metric=m["name"]))
            if value is None:
                log(f"per-layer {m['name']}: nothing to read in this "
                    f"run's trace and counters; left out of the line")
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = tr.breakdown()
        if keep_trace:
            shutil.copytree(r.trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(r.trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = out["memory_peak_bytes"]

    checks = {name: {"value": value, "limit": limit}
              for name, value, limit in out["checks"]}
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["info"] = out.get("info", {})
    line["checks"] = checks
    return line


def report(line: dict) -> None:
    """Checks as the last lines of stderr, the result as the last line of
    stdout."""
    for name, c in line["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
