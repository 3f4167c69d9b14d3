"""Near-sensor serving gateway, end to end: sensor fleet -> micro-batcher ->
SC/binary frontend offload -> slot-batched LM decode -> telemetry report.

Run:  python examples/serve_sensors.py --endpoints 64 --duration 5
      [--frontend sc|binary|both] [--bits 4] [--rate 4.0]
      [--lm-arch rwkv6-7b] [--no-lm]

Prints throughput, p50/p99 latency, J/inference and link bytes/frame per
frontend — the sc frontend moves fewer bytes and burns less energy per
frame, which is the paper's near-sensor claim as a measured quantity.
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve.gateway import frontend as fe  # noqa: E402
from repro.serve.gateway.gateway import (GatewayConfig,  # noqa: E402
                                         MicroBatchGateway)
from repro.serve.gateway.sensors import FleetConfig, SensorFleet  # noqa: E402
from repro.serve.spec import ServeSpec, make_gateway  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def run_frames(events, frontend: str, bits: int, duration: float,
               tracer=None, metrics=None, slo=None, flight=None,
               incident=None, service_ms: float | None = None) -> dict:
    spec = fe.FrontendSpec(mode=frontend, bits=bits)
    cfg = GatewayConfig() if service_ms is None else \
        GatewayConfig(service_model="fixed",
                      fixed_service_s=service_ms / 1e3)
    gw = MicroBatchGateway(cfg, spec)
    gw.warmup()
    tel = gw.run(events, tracer=tracer, metrics=metrics, slo=slo,
                 flight=flight, incident=incident)
    tel.assert_conserved()
    if tracer is not None:
        tracer.assert_energy_conserved(tel)
    rep = tel.report(duration, kind="frame")
    rep["link_bytes_per_frame"] = fe.link_bytes_per_frame(spec)
    rep["compile_counts"] = gw.compile_counts()
    return rep


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--endpoints", type=int, default=64)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--frontend", default="both",
                    choices=("sc", "binary", "both"))
    ap.add_argument("--bits", type=int, default=4,
                    choices=range(2, 9),
                    help="stream-length exponent (energy model: 2..8)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="mean frames/s per endpoint")
    ap.add_argument("--lm-arch", default="rwkv6-7b")
    ap.add_argument("--no-lm", action="store_true",
                    help="skip the token-prompt path")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV slots (block pool + prefix sharing); "
                         "no-op for rwkv, which has O(1) state")
    ap.add_argument("--backend", default=None,
                    choices=("gather", "xla", "pallas", "cascade"),
                    help="paged decode-tick attention dataflow (with "
                         "--paged); default probes the platform.  "
                         "'cascade' attends shared radix prefixes once "
                         "per group instead of once per lane")
    ap.add_argument("--trace", action="store_true",
                    help="record per-request lifecycle spans + interval "
                         "metrics and export a Chrome trace-event JSON "
                         "(open in chrome://tracing or ui.perfetto.dev)")
    ap.add_argument("--trace-out", default="trace.json",
                    help="trace output path (with --trace); interval "
                         "metrics land next to it as <stem>_metrics.jsonl")
    ap.add_argument("--slo", action="store_true",
                    help="attach the SLO burn-rate monitor (SRE multi-window "
                         "ladder scaled to --duration): prints the run's "
                         "health verdict and any ok/warn/critical "
                         "transitions")
    ap.add_argument("--slo-ttft-ms", type=float, default=200.0,
                    help="TTFT objective target (with --slo)")
    ap.add_argument("--slo-queue-ms", type=float, default=100.0,
                    help="queue-wait objective target (with --slo)")
    ap.add_argument("--health-out", default=None,
                    help="write the run's health surface (metrics + SLO burn "
                         "state) as an OpenMetrics text exposition")
    ap.add_argument("--flight", action="store_true",
                    help="attach the always-on bounded flight recorder "
                         "(reservoir-sampled spans + exact tails; works "
                         "without --trace — spans flow through a "
                         "retention-free tracer into the ring)")
    ap.add_argument("--incident-dir", default=None,
                    help="arm incident auto-capture: SLO warn->critical, "
                         "drop bursts, energy mismatches write "
                         "schema-validated debug bundles here (inspect "
                         "with python -m repro.serve.obs.incident)")
    ap.add_argument("--service-ms", type=float, default=None,
                    help="pin the frame-path service time (fixed service "
                         "model) — deterministic overload for incident/SLO "
                         "demos and CI")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = metrics = slo_mon = flight = incident = None
    if args.trace or args.slo or args.health_out or args.flight \
            or args.incident_dir:
        from repro.serve import obs
        metrics = obs.MetricsRegistry(interval_s=max(args.duration / 50,
                                                     1e-3))
    if args.trace:
        tracer = obs.Tracer()
    if args.slo:
        slo_mon = obs.SLOMonitor(
            obs.SLOPolicy.default(period_s=args.duration,
                                  ttft_s=args.slo_ttft_ms / 1e3,
                                  queue_wait_s=args.slo_queue_ms / 1e3),
            tracer=tracer, metrics=metrics)
    if args.flight or args.incident_dir:
        flight = obs.FlightRecorder()

    prompt_frac = 0.0 if args.no_lm else 0.125
    fleet = SensorFleet(FleetConfig(
        n_endpoints=args.endpoints, frame_rate_hz=args.rate,
        prompt_fraction=prompt_frac))
    events = fleet.events(args.duration)
    n_frames = sum(a.kind == "frame" for a in events)
    n_prompts = len(events) - n_frames
    print(f"fleet: {args.endpoints} endpoints, "
          f"~{fleet.offered_load_hz():.0f} req/s offered, "
          f"{n_frames} frames + {n_prompts} prompts over "
          f"{args.duration:.0f}s (virtual)")

    # -- frame path: micro-batched hybrid LeNet, sc vs binary offload -------
    frontends = ("sc", "binary") if args.frontend == "both" \
        else (args.frontend,)
    # one obs attachment (tracer/metrics/SLO monitor), one serving path:
    # the LM prompt path when it runs (the full lifecycle —
    # queue/prefill/decode — is the richer surface), else the first frame
    # frontend
    lm_path = bool(not args.no_lm and n_prompts)
    trace_lm = bool(args.trace and lm_path)
    # exactly one serving path owns the incident pipeline (it subscribes
    # to the SLO pressure signal at construction): the LM gateway builds
    # its own via ServeSpec(incident_dir=...); the frame path gets one
    # here only when it is the obs surface
    if args.incident_dir and not lm_path:
        incident = obs.IncidentCapture(args.incident_dir, flight=flight,
                                       slo=slo_mon, metrics=metrics)
    reports = {}
    for i, f in enumerate(frontends):
        frame_obs = not lm_path and i == 0
        reports[f] = run_frames(events, f, args.bits, args.duration,
                                tracer=tracer if frame_obs else None,
                                metrics=metrics if frame_obs else None,
                                slo=slo_mon if frame_obs else None,
                                flight=flight if frame_obs else None,
                                incident=incident if frame_obs else None,
                                service_ms=args.service_ms)
        r = reports[f]
        if not r["completed"]:
            print(f"[{f:6s}] no frames completed "
                  f"(offered {n_frames}, dropped {r['dropped']})")
            continue
        print(f"[{f:6s}] {r['throughput_hz']:7.1f} frames/s  "
              f"p50 {r['p50_latency_ms']:6.2f} ms  "
              f"p99 {r['p99_latency_ms']:6.2f} ms  "
              f"{r['mean_energy_nj']:7.2f} nJ/inference "
              f"({r['j_per_inference']:.3e} J)  "
              f"link {r['link_bytes_per_frame']:4d} B/frame  "
              f"dropped {r['dropped']}")
    if len(reports) == 2 and all(r["completed"] for r in reports.values()):
        s, b = reports["sc"], reports["binary"]
        assert s["link_bytes_per_frame"] < b["link_bytes_per_frame"]
        print(f"sc frontend: {b['link_bytes_per_frame']/s['link_bytes_per_frame']:.1f}x "
              f"fewer link bytes/frame, "
              f"{b['mean_energy_nj']/s['mean_energy_nj']:.1f}x lower "
              f"energy/inference than the binary partition")

    # -- LM path: prompts through the family-generic slot batcher -----------
    if not args.no_lm and n_prompts:
        import jax.numpy as jnp
        cfg = configs.smoke_config(args.lm_arch)
        params, _ = lm.init(jax.random.key(0), cfg, {})
        extras = None              # modality stubs for encdec/vlm prefill
        if cfg.family == "encdec":
            extras = lambda: {"enc_embed": jnp.zeros(       # noqa: E731
                (1, cfg.enc_len, cfg.d_model), jnp.bfloat16)}
        elif cfg.family == "vlm":
            extras = lambda: {"vision_embed": jnp.zeros(    # noqa: E731
                (1, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)}
        paged = args.paged and cfg.family != "rwkv"
        spec = ServeSpec(n_slots=args.slots, max_len=64, paged=paged,
                         block_size=8, backend=args.backend if paged
                         else None, max_new_tokens=8,
                         tracer=tracer if trace_lm else None,
                         metrics=metrics, slo=slo_mon, flight=flight,
                         incident_dir=args.incident_dir)
        pgw = make_gateway(cfg, params, spec, extras=extras)
        if args.incident_dir:
            incident = pgw.incident
        pgw.warmup(fleet.cfg.prompt_lens, cfg.vocab)
        tel = pgw.run(events)
        if trace_lm:
            tracer.assert_energy_conserved(tel)
        r = tel.report(args.duration, kind="prompt")
        print(f"[lm:{cfg.family}] {r['completed']} prompts  "
              f"{r['throughput_hz']:6.1f} req/s  "
              f"p50 {r['p50_latency_ms']:6.1f} ms  "
              f"p99 {r['p99_latency_ms']:6.1f} ms  "
              f"{r.get('j_per_inference', 0.0):.2e} J/req  "
              f"dropped {r['dropped']}  "
              f"(slot batcher: {args.slots} slots, "
              f"family={cfg.family}, kv={'paged' if paged else 'dense'})")
        if paged and "pool" in r:
            p = r["pool"]
            print(f"[lm:pool] peak {p['peak_blocks_in_use']}"
                  f"/{p['num_blocks']} blocks in use, "
                  f"peak {p['peak_bytes_saved_vs_dense'] / 1024:.0f} KiB "
                  f"saved vs dense, "
                  f"{p['blocks_cached']} cached at drain, "
                  f"prefix hit rate {p['prefix_hit_rate']:.0%}, "
                  f"{p['evictions']} evictions, "
                  f"{p['cow_copies']} CoW copies")
            skipped = p.get("prefill_tokens_skipped", 0)
            if p.get("prefill_tokens_total"):
                print(f"[lm:prefill] {skipped}"
                      f"/{p['prefill_tokens_total']} prompt tokens "
                      f"skipped via prefix-hit chunked prefill "
                      f"({r.get('prefill_energy_saved_nj', 0.0):.1f} nJ "
                      f"frontend energy saved)")
        if "ttft_p50_ms" in r:
            print(f"[lm:slo] ttft p50 {r['ttft_p50_ms']:.1f} / "
                  f"p99 {r['ttft_p99_ms']:.1f} ms  "
                  f"tpot p50 {r['tpot_p50_ms']:.2f} / "
                  f"p99 {r['tpot_p99_ms']:.2f} ms  "
                  f"queue-wait p99 "
                  f"{r.get('queue_wait_p99_ms', 0.0):.1f} ms  "
                  f"(n={r['slo_n_samples']})")

    # -- health verdict + OpenMetrics exposition ----------------------------
    if slo_mon is not None:
        rep = slo_mon.report()
        burns = "  ".join(f"burn_{k}={v:.2f}"
                          for k, v in sorted(rep["burns"].items()))
        print(f"[health] state={rep['state']}  "
              f"transitions={len(rep['transitions'])}  {burns}")
        for tr_ in rep["transitions"]:
            print(f"[health]   t={tr_['t']:.3f}s {tr_['from']} -> "
                  f"{tr_['to']} (worst: {tr_['objective']})")
    if args.health_out:
        # the scrape surface must declare what the run promised: cascade
        # runs must expose the repro_cascade_* grouping families
        require = None
        if args.backend == "cascade" and lm_path and args.paged:
            require = [f"repro_cascade_{k}" for k in
                       ("groups", "grouped_lanes", "prefix_rows",
                        "prefix_rows_flat")]
        text = obs.write_openmetrics(args.health_out, metrics, slo_mon,
                                     require=require)
        print(f"[health] {len(text.splitlines())} OpenMetrics lines "
              f"(schema-validated"
              + (f", {len(require)} required families" if require else "")
              + f") -> {args.health_out}")

    # -- flight recorder + incident forensics -------------------------------
    if flight is not None:
        acct = flight.snapshot()["accounting"]
        print(f"[flight] ring: {acct['spans_kept']}/{acct['spans_seen']} "
              f"spans (reservoir), {acct['instants_kept']}"
              f"/{acct['instants_seen']} instants, "
              f"{acct['samples_kept']}/{acct['samples_seen']} "
              f"metric samples retained")
    if incident is not None:
        if incident.captures:
            for c in incident.captures:
                print(f"[incident] t={c['t']:.3f}s reason={c['reason']} "
                      f"-> {c['path']}")
            print(f"[incident] inspect with: python -m "
                  f"repro.serve.obs.incident inspect "
                  f"{incident.captures[0]['path']}")
        else:
            print(f"[incident] no triggers fired; bundles would land in "
                  f"{args.incident_dir}")

    # -- trace export: Perfetto-loadable, schema-validated ------------------
    if args.trace:
        out = pathlib.Path(args.trace_out)
        obj = obs.write_chrome_trace(str(out), tracer, metrics)
        mpath = out.with_name(out.stem + "_metrics.jsonl")
        n = obs.write_metrics_jsonl(str(mpath), metrics)
        print(f"[trace] {len(obj['traceEvents'])} events -> {out} "
              f"(Chrome trace-event JSON, schema-validated; open in "
              f"ui.perfetto.dev); {n} metric snapshots -> {mpath}")


if __name__ == "__main__":
    main()
