"""Gateway load sweep: offered load vs achieved throughput / latency / energy.

Sweeps the fleet's per-endpoint rate for both frontend partitions and emits
BENCH_gateway.json (plus the usual CSV lines via common.emit), so the serving
perf trajectory accumulates across PRs.

Run:  PYTHONPATH=src python benchmarks/gateway_bench.py
      [--endpoints 32] [--duration 2] [--rates 2,8,32]
"""
import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import common  # noqa: E402

from repro.serve.gateway import frontend as fe  # noqa: E402
from repro.serve.gateway.gateway import GatewayConfig, MicroBatchGateway  # noqa: E402
from repro.serve.gateway.sensors import FleetConfig, SensorFleet  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--endpoints", type=int, default=32)
    ap.add_argument("--duration", type=float, default=2.0)
    ap.add_argument("--rates", default="2,8,32",
                    help="per-endpoint frame rates (Hz) to sweep")
    ap.add_argument("--bits", type=int, default=4)
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_gateway.json"))
    args = ap.parse_args()
    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]

    results = []
    for mode in ("sc", "binary"):
        spec = fe.FrontendSpec(mode=mode, bits=args.bits)
        gw = MicroBatchGateway(GatewayConfig(), spec)
        gw.warmup()
        for rate in rates:
            fleet = SensorFleet(FleetConfig(
                n_endpoints=args.endpoints, frame_rate_hz=rate))
            events = fleet.events(args.duration)
            tel = gw.run(events)
            tel.assert_conserved()
            rep = tel.report(args.duration, kind="frame")
            rec = {
                "frontend": mode,
                "bits": args.bits,
                "endpoints": args.endpoints,
                "offered_hz": fleet.offered_load_hz(),
                "achieved_hz": rep["throughput_hz"],
                "p50_latency_ms": rep.get("p50_latency_ms", 0.0),
                "p99_latency_ms": rep.get("p99_latency_ms", 0.0),
                "j_per_inference": rep.get("j_per_inference", 0.0),
                "link_bytes_per_frame": fe.link_bytes_per_frame(spec),
                "dropped": rep["dropped"],
            }
            results.append(rec)
            common.emit(
                f"gateway_{mode}_{rate:g}hz",
                rep.get("p99_latency_ms", 0.0) * 1e3,
                f"{rep['throughput_hz']:.1f}fps,"
                f"{rec['j_per_inference']:.3e}J,"
                f"{rec['link_bytes_per_frame']}B")
    common.emit_json(args.out, {"bench": "gateway", "results": results})


if __name__ == "__main__":
    main()
