"""Serving observability (serve/obs/): span tracer correctness, the
zero-callback disabled contract, bitwise span-energy conservation against
the telemetry ledger for both frontends and both serving paths, metrics
time-series, SLO stats in report(), drop reasons, the recompile detector,
and Chrome trace-event export validity."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.models import lm
from repro.serve import obs
from repro.serve.gateway import frontend as fe
from repro.serve.gateway.gateway import (GatewayConfig, MicroBatchGateway,
                                         PromptGateway)
from repro.serve.gateway.sensors import Arrival
from repro.serve.gateway.slots import (ContinuousBatcher, Request,
                                       make_adapter)
from repro.serve.gateway.telemetry import Telemetry
from repro.serve.shard import ShardedPromptGateway, build_slices

BS = 4

_SETUP_CACHE: dict = {}


def _setup(arch="stablelm_3b"):
    if arch not in _SETUP_CACHE:
        cfg = dataclasses.replace(configs.smoke_config(arch),
                                  param_dtype="float32")
        params, _ = lm.init(jax.random.key(0), cfg, {})
        _SETUP_CACHE[arch] = (cfg, params)
    return _SETUP_CACHE[arch]


def _slice_mesh(i: int) -> Mesh:
    devs = jax.devices()
    return Mesh(np.asarray([devs[i % len(devs)]]), ("model",))


def _prompt_arrivals(cfg, n, plen=8, seed=0, dt=0.001):
    rng = np.random.default_rng(seed)
    return [Arrival(t=i * dt, uid=i, endpoint=0, kind="prompt",
                    payload=rng.integers(0, cfg.vocab, plen)
                    .astype(np.int32)) for i in range(n)]


def _frame_arrivals(n, seed=0, dt=0.0005):
    rng = np.random.default_rng(seed)
    return [Arrival(t=i * dt, uid=i, endpoint=0, kind="frame",
                    payload=rng.integers(0, 255, (28, 28, 1))
                    .astype(np.uint8)) for i in range(n)]


# ==========================================================================
# Tracer unit behavior.
# ==========================================================================

def test_tracer_strict_nesting_enforced_at_record_time():
    tr = obs.Tracer()
    tr.clock.advance(1.0)
    tr.begin("a", tid=7)
    tr.clock.advance(2.0)
    tr.begin("b", tid=7)
    with pytest.raises(AssertionError):
        tr.end("a", tid=7)              # b is innermost: a may not close
    tr.clock.advance(3.0)
    tr.end("b", tid=7)
    tr.end("a", tid=7)
    with pytest.raises(AssertionError):
        tr.end("a", tid=7)              # nothing open
    tr.assert_nested()
    spans = {s["name"]: s for s in tr.spans()}
    assert spans["a"]["ts"] == 1.0 and spans["a"]["dur"] == 2.0
    assert spans["b"]["ts"] == 2.0 and spans["b"]["dur"] == 1.0


def test_tracer_open_span_fails_nesting_check():
    tr = obs.Tracer()
    tr.begin("left_open", tid=1)
    with pytest.raises(AssertionError, match="open spans"):
        tr.assert_nested()


def test_sim_clock_is_monotone():
    c = obs.SimClock()
    c.advance(2.0)
    c.advance(1.0)                      # going backwards is a no-op
    assert c.t == 2.0


def test_wall_clock_stamps_are_monotone_wall_time():
    c = obs.WallClock()
    t0 = time.perf_counter()
    a = c.t
    time.sleep(0.002)
    b = c.t
    t1 = time.perf_counter()
    assert t0 <= a < b <= t1 and b - a >= 0.002
    c.advance(b + 100.0)                # a wall clock is not advanced
    assert c.t < b + 100.0
    tr = obs.Tracer(clock=c)
    tr.anchor()                         # no virtual window to anchor
    t2 = time.perf_counter()
    stamp = tr.now()
    assert t2 <= stamp <= time.perf_counter()
    tr.release()
    tr.begin("a", tid=1)
    time.sleep(0.001)
    tr.end("a", tid=1)
    (span,) = tr.spans("a")
    assert span["ts"] >= t2 and span["dur"] >= 0.001


def test_span_nests_records_end_args_and_rejects_bad_close():
    tr = obs.Tracer(clock=obs.WallClock())
    c0 = obs.callback_count()
    with tr.span("outer", tid=3, active=2) as sp:
        with tr.span("inner", tid=3):
            pass
        sp.set(finished=1)
    assert obs.callback_count() > c0    # every entry point charges
    spans = {s["name"]: s for s in tr.spans()}
    assert spans["outer"]["args"] == {"active": 2, "finished": 1}
    outer, inner = spans["outer"], spans["inner"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    tr.assert_nested()
    # a span left open inside the body makes the close fail, as end does
    with pytest.raises(AssertionError, match="must nest"):
        with tr.span("x", tid=4):
            tr.begin("y", tid=4)
    assert tr.innermost(tid=4) == "y"


# ==========================================================================
# Zero-cost-when-disabled: no tracer attached -> zero obs callbacks.
# ==========================================================================

def test_disabled_tracing_makes_zero_callbacks():
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    gw = PromptGateway(ContinuousBatcher(ad), max_new_tokens=3)
    gw.warmup((4, 8))
    c0 = obs.callback_count()
    tel = gw.run(_prompt_arrivals(cfg, 4))
    assert tel.report(1.0, "prompt")["completed"] == 4
    # SLO stamps still work without a tracer (bare SimClock path) ...
    assert all(r.t_admit >= 0 for r in tel.records)
    # ... and not one Python-level tracer callback was made
    assert obs.callback_count() == c0


def test_disabled_tracing_direct_batcher_zero_callbacks():
    """The tick's scoped spans (admit, tick.prepare / dispatch / sample,
    retire, prefill, prefill_chunk) make no obs call with no tracer."""
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    b = ContinuousBatcher(ad)
    rng = np.random.default_rng(4)
    c0 = obs.callback_count()
    for uid in range(3):
        b.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab, 6).astype(np.int32), max_new_tokens=3))
    done = b.run()
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert all(len(r.generated) == 3 for r in done)
    assert obs.callback_count() == c0


def test_disabled_tracing_frame_path_zero_callbacks():
    spec = fe.FrontendSpec(mode="sc", bits=4)
    gw = MicroBatchGateway(GatewayConfig(bucket_sizes=(1, 2, 4),
                                         service_model="fixed",
                                         fixed_service_s=0.001), spec)
    gw.warmup()
    c0 = obs.callback_count()
    tel = gw.run(_frame_arrivals(8))
    assert tel.report(1.0, "frame")["completed"] == 8
    assert obs.callback_count() == c0


# ==========================================================================
# Span energy attribution sums bitwise to the conserved ledger.
# ==========================================================================

@pytest.mark.parametrize("mode", ["sc", "binary"])
def test_frame_span_energy_conserved_bitwise(mode):
    spec = fe.FrontendSpec(mode=mode, bits=4)
    gw = MicroBatchGateway(GatewayConfig(bucket_sizes=(1, 2, 4),
                                         service_model="fixed",
                                         fixed_service_s=0.001), spec)
    gw.warmup()
    tracer = obs.Tracer()
    tel = gw.run(_frame_arrivals(10), tracer=tracer)
    tel.assert_conserved()
    tracer.assert_nested()
    tracer.assert_energy_conserved(tel)     # float equality, not isclose
    spans = tracer.request_spans()
    assert set(spans) == {r.uid for r in tel.records}
    # every lifecycle stage is present and the span covers arrival -> done
    for r in tel.records:
        s = spans[r.uid]
        assert s["ts"] == r.t_arrival
        assert s["ts"] + s["dur"] == pytest.approx(r.t_done, abs=1e-12)
    for name in ("sensor_link", "queue_wait", "serve", "batch"):
        assert tracer.spans(name)


def test_prompt_span_energy_conserved_bitwise_and_slo_stats():
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    tracer = obs.Tracer()
    metrics = obs.MetricsRegistry(interval_s=1e-4)
    gw = PromptGateway(ContinuousBatcher(ad), max_new_tokens=3,
                       tracer=tracer, metrics=metrics)
    c0 = obs.callback_count()
    gw.warmup((4, 8))
    assert obs.callback_count() == c0       # warmup is never traced
    tel = gw.run(_prompt_arrivals(cfg, 5))
    tel.assert_conserved()
    tracer.assert_nested()
    tracer.assert_energy_conserved(tel)
    assert set(tracer.request_spans()) == {r.uid for r in tel.records}
    assert tracer.spans("prefill") and tracer.spans("decode")
    assert tracer.spans("prefill_chunk")    # paged fold chunks traced
    assert tracer.spans("tick")             # engine track
    rep = tel.report(1.0, "prompt")
    assert rep["n_samples"] == 5 and rep["slo_n_samples"] == 5
    for k in ("ttft_p50_ms", "ttft_p99_ms", "tpot_p50_ms", "tpot_p99_ms",
              "queue_wait_p50_ms", "queue_wait_p99_ms"):
        assert rep[k] >= 0.0
    # interval time-series rode into the report (pool occupancy + queue)
    series = rep["series"]
    assert len(series) >= 2
    assert all("pool_blocks_in_use" in s and "queue_depth" in s
               for s in series)


def test_prefix_hit_chunks_marked_in_trace():
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=1, max_len=16, paged=True,
                      block_size=BS)
    tracer = obs.Tracer()
    gw = PromptGateway(ContinuousBatcher(ad), max_new_tokens=2,
                       tracer=tracer)
    gw.warmup((4,))
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, cfg.vocab, 2 * BS).astype(np.int32)
    arrs = [
        Arrival(t=0.0, uid=0, endpoint=0, kind="prompt",
                payload=np.concatenate([prefix, [1, 2]]).astype(np.int32)),
        Arrival(t=10.0, uid=1, endpoint=0, kind="prompt",
                payload=np.concatenate([prefix, [3, 4]]).astype(np.int32)),
    ]
    tel = gw.run(arrs)
    tracer.assert_energy_conserved(tel)
    resumes = [e for e in tracer.events if e["name"] == "prefix_resume"]
    assert len(resumes) == 1                # only the warm request resumed
    assert resumes[0]["args"]["blocks"] == 2
    assert resumes[0]["args"]["tokens_skipped"] == 2 * BS
    assert resumes[0]["tid"] == 1           # on the warm request's track
    # the warm request folded fewer chunks than the cold one
    chunks = tracer.spans("prefill_chunk")
    cold = [c for c in chunks if c["tid"] == 0]
    warm = [c for c in chunks if c["tid"] == 1]
    assert len(warm) < len(cold)
    assert all(c["args"]["prefix_hit"] is False for c in chunks)


def test_sharded_trace_covers_migration_and_conserves_energy():
    cfg, params = _setup()
    slices = build_slices(cfg, params, [_slice_mesh(0), _slice_mesh(1)],
                          n_slots=1, max_len=16, block_size=BS,
                          num_blocks=9)
    tracer = obs.Tracer()
    gw = ShardedPromptGateway(slices, max_new_tokens=8, max_queue=128,
                              tracer=tracer)
    gw.warmup((4, 8))
    rng = np.random.default_rng(51)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS).astype(np.int32)
    a = Request(uid=0, prompt=prefix, max_new_tokens=8)
    gw.submit(a)
    gw.slices[0].batcher.step()             # admit A, untraced
    b = Request(uid=1, prompt=rng.integers(0, cfg.vocab, size=6,
                                           dtype=np.int32),
                max_new_tokens=2)
    gw.submit(b)
    c = Request(uid=2, prompt=np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=3, dtype=np.int32)]),
        max_new_tokens=2)
    gw.submit(c)
    tel = gw.run([])                        # drain under auto-rebalance
    tel.assert_conserved()
    tracer.assert_nested()
    # every completed uid has a request span (A's opened late) and the
    # span energies — incl. A's migration part — reproduce the ledger
    tracer.assert_energy_conserved(tel)
    assert gw.migrations >= 1
    mig = tracer.spans("migrate")
    assert len(mig) == gw.migrations
    assert mig[0]["tid"] == 0 and mig[0]["args"]["bytes"] > 0
    moved = tracer.request_spans()[0]["args"]["energy_parts"]
    assert moved["migration_nj"] > 0.0
    # each slice ticks on its own engine track (pid 1 + slice_idx)
    tick_pids = {e["pid"] for e in tracer.spans("tick")}
    assert tick_pids <= {1, 2} and 1 in tick_pids


# ==========================================================================
# Telemetry satellites: drop reasons, report guards, series passthrough.
# ==========================================================================

def test_drop_reasons_and_legacy_tuple_shape():
    tel = Telemetry()
    tel.drop(7, "frame")                    # legacy 2-arg call still works
    tel.drop(8, "prompt", "queue_full", 1.5)
    assert [d[:2] for d in tel.dropped] == [(7, "frame"), (8, "prompt")]
    rep = tel.report(1.0)
    assert rep["dropped"] == 2
    assert rep["dropped_by_reason"] == {"unspecified": 1, "queue_full": 1}
    assert tel.report(1.0, "prompt")["dropped_by_reason"] == \
        {"queue_full": 1}


def test_gateway_drop_carries_reason_and_time():
    spec = fe.FrontendSpec(mode="sc", bits=4)
    gw = MicroBatchGateway(GatewayConfig(bucket_sizes=(1, 2),
                                         max_queue=2,
                                         service_model="fixed",
                                         fixed_service_s=1.0), spec)
    gw.warmup()
    tel = gw.run(_frame_arrivals(16, dt=1e-5))
    rep = tel.report(1.0, "frame")
    assert rep["dropped"] > 0
    assert rep["dropped_by_reason"] == {"queue_full": rep["dropped"]}
    assert all(d[2] == "queue_full" and d[3] > 0 for d in tel.dropped)


def test_report_zero_duration_and_tiny_samples_guarded():
    tel = Telemetry()
    rep = tel.report(0.0)                   # must not divide by zero
    assert rep["throughput_hz"] == 0.0 and rep["n_samples"] == 0
    assert "p99_latency_ms" not in rep      # no percentile claims on n=0
    rep = tel.report(-1.0)
    assert rep["throughput_hz"] == 0.0


def test_report_series_passthrough():
    tel = Telemetry()
    tel.record_series([{"t": 0.0, "q": 1}, {"t": 0.1, "q": 2}])
    assert tel.report(1.0)["series"] == [{"t": 0.0, "q": 1},
                                         {"t": 0.1, "q": 2}]


# ==========================================================================
# Metrics registry.
# ==========================================================================

def test_metrics_counters_gauges_sources_and_interval():
    m = obs.MetricsRegistry(interval_s=0.1)
    depth = {"v": 3}
    m.register("queue_depth", lambda: depth["v"])
    m.inc("completed")
    m.inc("completed", 2)
    m.set_gauge("load", 0.5)
    assert m.maybe_sample(0.0)              # first call always samples
    assert not m.maybe_sample(0.05)         # inside the interval
    depth["v"] = 9
    assert m.maybe_sample(0.2)
    assert len(m.samples) == 2
    assert m.samples[0] == {"t": 0.0, "completed": 3.0, "load": 0.5,
                            "queue_depth": 3}
    assert m.samples[1]["queue_depth"] == 9
    ts, vs = m.series("queue_depth")
    assert ts == [0.0, 0.2] and vs == [3, 9]


def test_metrics_percentiles_carry_sample_count():
    m = obs.MetricsRegistry()
    assert m.percentiles("lat") == {"n": 0, "n_dropped": 0}
    for v in (1.0, 2.0, 3.0):
        m.observe("lat", v)
    p = m.percentiles("lat")
    assert p["n"] == 3 and p["p50"] == 2.0
    assert p["n_dropped"] == 0      # under the cap: summary is exact


# ==========================================================================
# Recompile detector.
# ==========================================================================

def test_recompile_detector_steady_state_and_leak():
    f = jax.jit(lambda x: x + 1)
    g = jax.jit(lambda x: x * 2)
    f(jnp.zeros(2))
    g(jnp.zeros(2))
    det = obs.RecompileDetector()
    det.track("t", {"f": f, "g": g})
    det.snapshot()
    f(jnp.ones(2))                          # same shape: cached
    assert det.steady_state_recompiles() == 0
    f(jnp.zeros(3))                         # new shape: a recompile
    assert det.steady_state_recompiles() == 1
    rep = det.report()
    assert rep["recompiles_by_fn"] == {"t.f": 1}
    assert rep["tracked_executables"] == 2
    with pytest.raises(AssertionError):
        det.track("bad", {"notjit": lambda x: x})


def test_gateway_jit_fns_zero_steady_state_recompiles():
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    gw = PromptGateway(ContinuousBatcher(ad), max_new_tokens=3)
    gw.warmup((8,))
    det = obs.RecompileDetector()
    det.track("gateway", gw.jit_fns())
    det.snapshot()
    gw.run(_prompt_arrivals(cfg, 4))
    assert det.steady_state_recompiles() == 0, det.report()


# ==========================================================================
# Exporters.
# ==========================================================================

def test_chrome_trace_export_is_valid_and_loadable(tmp_path):
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    tracer = obs.Tracer()
    metrics = obs.MetricsRegistry(interval_s=1e-4)
    gw = PromptGateway(ContinuousBatcher(ad), max_new_tokens=2,
                       tracer=tracer, metrics=metrics)
    gw.warmup((8,))
    gw.run(_prompt_arrivals(cfg, 3))
    path = tmp_path / "trace.json"
    obj = obs.write_chrome_trace(str(path), tracer, metrics)
    assert obs.validate_chrome_trace(obj) == []
    with open(path) as f:
        loaded = json.load(f)               # round-trips as plain JSON
    assert obs.validate_chrome_trace(loaded) == []
    names = {e["name"] for e in loaded["traceEvents"]}
    assert {"request", "prefill", "decode", "tick",
            "metrics", "process_name"} <= names
    # counter tracks carry the sampled metrics
    cs = [e for e in loaded["traceEvents"] if e["ph"] == "C"]
    assert cs and all("queue_depth" in e["args"] for e in cs)
    mpath = tmp_path / "metrics.jsonl"
    n = obs.write_metrics_jsonl(str(mpath), metrics)
    assert n == len(metrics.samples) > 0
    lines = [json.loads(ln) for ln in mpath.read_text().splitlines()]
    assert len(lines) == n and all("t" in ln for ln in lines)


def test_chrome_trace_validator_catches_structural_breaks():
    assert obs.validate_chrome_trace([]) == ["trace is not a JSON object"]
    assert obs.validate_chrome_trace({}) == \
        ["missing/invalid 'traceEvents' array"]
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": 0.0},
        {"name": "y", "ph": "Z", "pid": 0, "tid": 0, "ts": "no"},
    ]}
    errs = obs.validate_chrome_trace(bad)
    assert any("missing numeric dur" in e for e in errs)
    assert any("unknown phase" in e for e in errs)
    assert any("non-numeric ts" in e for e in errs)
    with pytest.raises(AssertionError, match="invalid trace"):
        obs.write_chrome_trace("/dev/null", obs.Tracer())  # empty events


# ==========================================================================
# Wall-clock spans on the profiler's host plane; queue wait from submit.
# ==========================================================================

def _host_events(trace_dir) -> list[tuple[str, int, int]]:
    import glob
    import os
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro:")]


def test_tick_spans_on_the_profiler_host_plane(tmp_path):
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    b = ContinuousBatcher(ad)
    rng = np.random.default_rng(5)

    def prompt():
        return rng.integers(0, cfg.vocab, 6).astype(np.int32)
    b.submit(Request(uid=0, prompt=prompt(), max_new_tokens=2))
    b.run()                                 # compile outside the trace
    tracer = obs.Tracer(clock=obs.WallClock())
    b.tracer = ad.tracer = tracer
    b.submit(Request(uid=1, prompt=prompt(), max_new_tokens=4))
    jax.profiler.start_trace(str(tmp_path))
    b.step()                                # admit, decode, retire
    jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    (tick,) = [e for e in events if e[0] == "repro:tick"]
    for name in ("repro:admit", "repro:prefill", "repro:prefill_chunk",
                 "repro:tick.prepare", "repro:tick.dispatch",
                 "repro:tick.sample", "repro:retire"):
        inside = [e for e in events if e[0] == name]
        assert inside, name
        assert all(tick[1] <= a and e <= tick[2] for _, a, e in inside)
    # the tick's children run one after another
    order = [e[0] for e in sorted(events, key=lambda e: e[1])
             if e[0] in ("repro:admit", "repro:tick.prepare",
                         "repro:tick.dispatch", "repro:tick.sample",
                         "repro:retire")]
    assert order == ["repro:admit", "repro:tick.prepare",
                     "repro:tick.dispatch", "repro:tick.sample",
                     "repro:retire"]
    # the in-memory spans: the tick's children on the engine track
    kids = {s["name"]: s for s in tracer.spans() if s["pid"] == 1}
    assert kids["tick"]["args"] == {"active": 1, "finished": 0}
    assert kids["admit"]["args"] == {"admitted": 1, "prompt_tokens": 6}
    assert kids["tick.prepare"]["args"] == {"active": 1, "live_tokens": 6}
    assert {"tick.dispatch", "tick.sample", "retire"} <= set(kids)
    b.tracer = ad.tracer = None


def test_moe_tick_spans_count_expert_rows_and_picks():
    """An MoE adapter's ``tick`` and ``tick.prepare`` spans carry the rows
    its expert FFN computes in the tick (every lane, one-token groups:
    2 lanes x 8 experts x capacity 2 x 2 MoE layers) and the router picks
    the active lanes need (1 lane x top-2 x 2 layers), counted on the host,
    and the adapter keeps their running totals."""
    cfg, params = _setup("moonshot_v1_16b_a3b")
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    b = ContinuousBatcher(ad)
    tracer = obs.Tracer(clock=obs.WallClock())
    b.tracer = ad.tracer = tracer
    b.submit(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                     max_new_tokens=3))
    b.step()
    b.step()
    want = {"moe_rows": 64, "moe_picks": 4}
    for name in ("tick", "tick.prepare"):
        spans = tracer.spans(name)
        assert len(spans) == 2
        for sp in spans:
            assert {k: sp["args"][k] for k in want} == want, name
    assert (ad.moe_rows_total, ad.moe_picks_total) == (128, 8)
    b.tracer = ad.tracer = None


def test_pallas_tick_spans_count_attention_blocks():
    """A Pallas adapter's ``tick`` and ``tick.prepare`` spans carry the
    table entries the dense paged kernel computes and the live blocks the
    lanes hold, counted on the host by ``engine.decode_attn_work``, and
    the adapter keeps their running totals.  Smoke stablelm: 4 layers,
    4 entries of 4 rows, so one grid step of 4 entries a lane; lanes of
    6 and 0 cached tokens attend 7 and 1 rows (2 and 1 live blocks), then
    8 and 1."""
    cfg, params = _setup()
    assert cfg.n_layers == 4
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS, backend="pallas")
    b = ContinuousBatcher(ad)
    tracer = obs.Tracer(clock=obs.WallClock())
    b.tracer = ad.tracer = tracer
    b.submit(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                     max_new_tokens=3))
    b.step()
    b.step()
    want = {"attn_blocks_read": 4 * 2 * 4, "attn_blocks_live": 4 * 3}
    for name in ("tick", "tick.prepare"):
        spans = tracer.spans(name)
        assert len(spans) == 2
        for sp in spans:
            assert {k: sp["args"][k] for k in want} == want, name
            assert "moe_rows" not in sp["args"]
    assert (ad.attn_blocks_read_total, ad.attn_blocks_live_total) == (64, 24)
    b.tracer = ad.tracer = None


def test_queue_wait_runs_from_submit_when_driven_directly():
    cfg, params = _setup()
    ad = make_adapter(cfg, params, n_slots=1, max_len=16, paged=True,
                      block_size=BS)
    b = ContinuousBatcher(ad)
    tracer = obs.Tracer(clock=obs.WallClock())
    b.tracer = ad.tracer = tracer
    t0 = time.perf_counter()
    b.submit(Request(uid=7, prompt=np.arange(5, dtype=np.int32),
                     max_new_tokens=2))
    time.sleep(0.01)
    b.run()
    (wait,) = tracer.spans("queue_wait")
    assert t0 <= wait["ts"] and wait["dur"] >= 0.01
    assert tracer.innermost(tid=7) == "request"     # no gateway closes it
    assert "late_open" not in tracer.open_spans()[0]["args"]
    b.tracer = ad.tracer = None
