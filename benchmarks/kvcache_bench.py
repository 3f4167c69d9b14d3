"""Dense vs paged KV slots at a fixed simulated HBM budget.

Gives both layouts the same KV byte budget, offers the same prompt trace
(mixed lengths, a shared system-prefix cohort), and reports what each
sustains: max concurrent slots, p99 latency, completion, and — paged only —
the pool counters (prefix-hit rate, bytes saved vs dense, evictions).

Dense spends the budget on whole ``max_len`` slots; the pool spends it on
blocks, so short requests stop paying for their worst case and shared
prefixes stop paying at all.  The acceptance bar (checked by
``benchmarks/check_bench.py`` in CI) is ``paged.max_concurrent_slots >
dense.max_concurrent_slots`` at equal bytes.

A second series, ``decode_tick``, races the PR 2 gather tick against the
in-place tick (``engine.decode_step_paged``) at growing chain depth:
tokens/s on frozen steady state plus the dataflow-implied arena-bytes
proxy.  The CI trend gate requires the in-place tick not to lose at
``nb_max >= 4`` and its bytes proxy to stay strictly below the gather
tick's.

A third series, ``sharded_tick`` (``--sharded``), scales the paged stack
over gateway slices (serve/shard/): at a fixed per-device block budget it
compares one device against ``min(8, jax.device_count())`` slices —
aggregate concurrent slots, aggregate tokens/s, routing counters — and
replays a mid-decode cross-slice block migration, reporting its byte cost
and whether the migrated lane's logits stayed bitwise.  Run it under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the ``sharded`` CI
job does) for a real multi-device comparison; the CI gate requires the
8-slice aggregate to beat the single device's concurrency and the
migration to be bitwise.

A fourth series, ``disagg`` (``--disagg``), is the PR 8 acceptance run: at
an equal device budget it drives the same short-decode streams plus a
long-prompt prefill burst through a colocated 8-slice gateway and through
the same slices under ``RolePlan.split(2, 6)`` (2 prefill-only slices
handing finished prompts off to 6 decode-only slices).  The gated quantity
is per-role p99 tick latency: decode-role ticks structurally never contain
admission's chunked prefill folds, so disaggregation must beat the
colocated gateway's all-slice tick p99 under the burst, with every request
completing in both modes and every disagg request arriving via handoff.
``--disagg`` writes its own payload (``BENCH_disagg.json`` unless ``--out``
is given) instead of the kvcache one.

A fifth series, ``cascade`` (``--cascade``), is the PR 9 acceptance run:
lanes sharing a radix prefix decode through the flat in-place tick
(``backend="xla"``, every lane re-attends the whole prefix) and through
the cascade tick (``backend="cascade"``, one multi-query prefix pass per
shared chain + per-lane suffix passes + log-sum-exp merge) over a
lanes x prefix-depth grid.  Gated quantities (check_bench): the grouping
stats must show prefix KV rows O(prefix) — constant in the lane count at
fixed depth, vs the flat tick's O(lanes x prefix) — the cascade bytes
proxy must undercut the flat proxy everywhere, and at the deepest
shared-prefix cell the cascade tick must win wall-clock.  Shallow cells
are reported, not gated: the merge/scatter overhead only amortizes once
the prefix dominates the tick (on CPU the crossover sits near 32 shared
blocks at 8 lanes; a TPU's per-block DMA moves it earlier).  ``--cascade``
writes its own payload (``BENCH_cascade.json`` by default).

Run:  PYTHONPATH=src python benchmarks/kvcache_bench.py
      [--arch stablelm_3b] [--budget-slots 4] [--requests 32] [--smoke]
      [--sharded | --disagg | --cascade]
"""
import argparse
import dataclasses
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import common  # noqa: E402
import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve import engine  # noqa: E402
from repro.serve.gateway.gateway import PromptGateway  # noqa: E402
from repro.serve.gateway.sensors import Arrival  # noqa: E402
from repro.serve.gateway.slots import ContinuousBatcher, make_adapter  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def kv_bytes_per_slot(cfg, max_len: int) -> int:
    """Sequence-axis cache bytes of one dense max_len slot."""
    arena = engine.init_paged_arena(cfg, 1, max_len, abstract=True)
    return sum(a.dtype.itemsize * int(np.prod(a.shape))
               for a in arena.values())


def make_trace(cfg, n_requests: int, max_len: int, n_new: int, seed: int = 0):
    """Short prompts, half sharing a common system prefix, arriving in one
    burst so concurrency is limited by memory, not by the arrival process."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, size=8, dtype=np.int32)
    arrivals = []
    for i in range(n_requests):
        tail = rng.integers(0, cfg.vocab, size=int(rng.integers(2, 7)),
                            dtype=np.int32)
        prompt = np.concatenate([prefix, tail]) if i % 2 == 0 else \
            np.concatenate([rng.integers(0, cfg.vocab, size=4,
                                         dtype=np.int32), tail])
        arrivals.append(Arrival(uid=i, t=0.0005 * i, endpoint=i % 8,
                                kind="prompt", payload=prompt))
    return arrivals


def run_layout(layout: str, cfg, params, arrivals, *, max_len: int,
               n_new: int, budget_bytes: int, block_size: int,
               warm_lens: tuple) -> dict:
    slot_bytes = kv_bytes_per_slot(cfg, max_len)
    block_bytes = kv_bytes_per_slot(cfg, block_size)
    if layout == "dense":
        n_slots = max(1, budget_bytes // slot_bytes)
        adapter = make_adapter(cfg, params, n_slots=n_slots, max_len=max_len)
    else:
        num_blocks = max(2, budget_bytes // block_bytes)   # incl. trash blk
        n_slots = min(len(arrivals), num_blocks - 1)
        adapter = make_adapter(cfg, params, n_slots=n_slots, max_len=max_len,
                               paged=True, block_size=block_size,
                               num_blocks=num_blocks)
    batcher = ContinuousBatcher(adapter)
    gw = PromptGateway(batcher, max_new_tokens=n_new,
                       max_queue=len(arrivals))
    gw.warmup(warm_lens, cfg.vocab)
    batcher.peak_active = 0                       # don't count warmup
    t0 = time.perf_counter()
    tel = gw.run(arrivals)
    wall = time.perf_counter() - t0
    tel.assert_conserved()
    rep = tel.report(max(wall, 1e-9), kind="prompt")
    out = {
        "layout": layout,
        "budget_bytes": budget_bytes,
        "kv_bytes_allocated": (n_slots * slot_bytes if layout == "dense"
                               else (num_blocks - 1) * block_bytes),
        "n_slots": n_slots,
        "max_concurrent_slots": batcher.peak_active,
        "completed": rep["completed"],
        "dropped": rep["dropped"],
        "p50_latency_ms": rep.get("p50_latency_ms", 0.0),
        "p99_latency_ms": rep.get("p99_latency_ms", 0.0),
        "j_per_inference": rep.get("j_per_inference", 0.0),
    }
    if layout == "paged":
        out["block_size"] = block_size
        out["pool"] = tel.pool
    return out


def decode_tick_series(cfg, params, *, block_size: int, n_slots: int,
                       nb_list: tuple, iters: int) -> list[dict]:
    """Gather tick vs in-place tick at growing chain depth.

    Every slot holds a chain spanning all ``nb_max`` blocks, so the gather
    tick pays its full O(slots * nb_max * bs) per-key materialization while
    the in-place tick reads the same chains through the block tables.
    Reports steady-state decode throughput (the jitted tick re-invoked on
    frozen state — fixed shapes, host-synced each call) and the
    dataflow-implied arena-bytes proxy from ``tick_bytes_proxy`` (what the
    TPU kernel's per-block DMA would stream; the XLA paths on CPU fuse
    their reads, so wall time is the honest metric there).
    """
    rng = np.random.default_rng(7)
    out = []
    for nb in nb_list:
        max_len = nb * block_size
        prompt = rng.integers(0, cfg.vocab, size=max_len - 2,
                              dtype=np.int32)
        rec = {"nb_max": nb, "block_size": block_size, "n_slots": n_slots}
        for mode in ("gather", "inplace"):
            ad = make_adapter(cfg, params, n_slots=n_slots, max_len=max_len,
                              paged=True, block_size=block_size,
                              chunked=False, inplace=(mode == "inplace"))
            for slot in range(n_slots):
                ad.insert(slot, prompt, max_new=2)
            rec[f"{mode}_bytes_proxy"] = ad.tick_bytes_proxy()[mode]
            toks = np.zeros(n_slots, np.int32)
            active = np.ones(n_slots, bool)
            ad.decode(toks, active)                    # compile + warm
            # best-of-3 batches: min is the noise-robust estimator, so the
            # CI trend gate measures the ticks, not the runner's scheduler
            dt = np.inf
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    ad.decode(toks, active)            # host-synced call
                dt = min(dt, time.perf_counter() - t0)
            rec[f"{mode}_tok_s"] = n_slots * iters / max(dt, 1e-9)
        rec["speedup"] = rec["inplace_tok_s"] / max(rec["gather_tok_s"],
                                                    1e-9)
        common.emit(f"decode_tick_nb{nb}",
                    1e6 * n_slots / rec["inplace_tok_s"],
                    f"{rec['speedup']:.2f}x_vs_gather")
        out.append(rec)
    return out


def sharded_tick_series(cfg, params, *, block_size: int) -> dict:
    """One device vs N single-device slices at a fixed per-device budget.

    The acceptance quantity is *aggregate concurrent slots*: each slice
    brings its own block pool, so the fleet's admissible working set
    scales with the slice count while no device holds more than
    ``budget`` blocks.  Wall-clock aggregate tokens/s is reported but not
    gated (on CPU the virtual devices share the same cores).  The series
    also replays a mid-decode migration between two slices and pins the
    migrated lane's logits bitwise against a stay-put oracle.
    """
    from repro.serve.gateway.slots import Request
    from repro.serve.shard import (ShardedPromptGateway, build_slices,
                                   migrate_slot)
    from repro.dist.sharding import slice_meshes
    from repro.launch import mesh as mesh_lib

    n_slices = min(8, jax.device_count())
    budget = 9                                  # 8 usable blocks per device
    max_len, max_new, n_req = 16, 4, 4 * n_slices
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab, size=6, dtype=np.int32)
               for _ in range(n_req)]
    arrivals = [Arrival(uid=i, t=0.0, endpoint=0, kind="prompt", payload=p)
                for i, p in enumerate(prompts)]
    rec = {"n_devices": jax.device_count(), "n_slices": n_slices,
           "budget_blocks_per_device": budget, "block_size": block_size}

    # single device, same per-device budget
    single = make_adapter(cfg, params, n_slots=8, max_len=max_len,
                          paged=True, block_size=block_size,
                          num_blocks=budget)
    sb = ContinuousBatcher(single)
    for i, p in enumerate(prompts):
        sb.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    t0 = time.perf_counter()
    done = sb.run()
    dt = time.perf_counter() - t0
    rec["single_slots"] = sb.peak_active
    rec["single_tok_s"] = sum(len(r.generated) for r in done) / max(dt, 1e-9)

    # N slices, each with the same per-device budget
    mesh = mesh_lib.make_serving_mesh(n_slices, model=1)
    slices = build_slices(cfg, params, mesh, n_slots=8, max_len=max_len,
                          block_size=block_size, num_blocks=budget)
    gw = ShardedPromptGateway(slices, max_new_tokens=max_new,
                              max_queue=4 * n_req)
    t0 = time.perf_counter()
    tel = gw.run(arrivals)
    dt = time.perf_counter() - t0
    rep = tel.report(max(dt, 1e-9), kind="prompt")
    rec["sharded_slots"] = gw.peak_active_total()
    rec["sharded_tok_s"] = rep["completed"] * max_new / max(dt, 1e-9)
    rec["sharded_gt_single"] = rec["sharded_slots"] > rec["single_slots"]
    rec["routing"] = dict(gw.routing)

    # mid-decode migration: bytes moved + bitwise continuation
    subs = slice_meshes(mesh)
    mk = lambda m=None: make_adapter(cfg, params, n_slots=2, max_len=max_len,
                                     paged=True, block_size=block_size,
                                     mesh=m)
    oracle, A, B = mk(), mk(subs[0]), mk(subs[min(1, len(subs) - 1)])
    ps = [rng.integers(0, cfg.vocab, size=s, dtype=np.int32) for s in (5, 9)]
    active = np.asarray([True, True])
    for slot, p in enumerate(ps):
        oracle.insert(slot, p, max_new=7)
        A.insert(slot, p, max_new=7)
    for _ in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        oracle.decode(forced, active)
        A.decode(forced, active)
    receipt = migrate_slot(A, 1, B, 1, ps[1])
    bitwise = True
    lane1 = np.asarray([False, True])
    for _ in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        oracle.decode(forced, active)
        B.decode(forced, lane1)
        bitwise &= bool(np.array_equal(np.asarray(oracle.last_logits)[1],
                                       np.asarray(B.last_logits)[1]))
    rec["migration_bytes"] = int(receipt.bytes_moved)
    rec["migration_blocks"] = int(receipt.blocks_moved)
    rec["migration_bitwise"] = bitwise
    common.emit("sharded_tick", 1e6 / max(rec["sharded_tok_s"], 1e-9),
                f"{rec['sharded_slots']}v{rec['single_slots']}slots,"
                f"{n_slices}slices,mig{'OK' if bitwise else 'DRIFT'}")
    return rec


def disagg_series(cfg, params, *, block_size: int) -> dict:
    """Colocated vs disaggregated gateway under a prefill burst.

    Mirrors the tests/test_disagg.py head-of-line bar: 8 single-device
    slices (re-using devices modulo the host's count; run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a real
    8-device comparison), 12 short decode-stream prompts plus 8 long
    burst prompts, equal block budget per slice in both modes.  The
    colocated gateway's ticks absorb admission's chunked folds; the
    disaggregated gateway's decode-role ticks never do, which is exactly
    the between-token latency a decode-bound serving tier sells.
    """
    from jax.sharding import Mesh
    from repro.serve.shard import (RolePlan, ShardedPromptGateway,
                                   build_slices)

    n_slices, max_len, max_new = 8, 36, 6
    plan = RolePlan.split(2, 6)
    rng = np.random.default_rng(61)
    short = [rng.integers(0, cfg.vocab, size=5, dtype=np.int32)
             for _ in range(12)]
    burst = [rng.integers(0, cfg.vocab, size=28, dtype=np.int32)
             for _ in range(8)]
    arrivals = [Arrival(uid=i, t=0.0, endpoint=0, kind="prompt", payload=p)
                for i, p in enumerate(short)]
    arrivals += [Arrival(uid=100 + i, t=0.0, endpoint=0, kind="prompt",
                         payload=p) for i, p in enumerate(burst)]
    devs = jax.devices()

    def run(roles):
        meshes = [Mesh(np.asarray([devs[i % len(devs)]]), ("model",))
                  for i in range(n_slices)]
        slices = build_slices(cfg, params, meshes, n_slots=2,
                              max_len=max_len, block_size=block_size)
        gw = ShardedPromptGateway(slices, max_new_tokens=max_new,
                                  max_queue=4 * len(arrivals), roles=roles,
                                  auto_rebalance=False)
        gw.warmup((4, 8))
        t0 = time.perf_counter()
        tel = gw.run(list(arrivals))
        wall = time.perf_counter() - t0
        return gw, tel.report(max(wall, 1e-9), kind="prompt")

    colo, crep = run(None)
    dis, drep = run(plan)
    results = [
        {"mode": "colocated", "completed": crep["completed"],
         "tick_p99_ms": colo.tick_latency_ms("all"),
         "prefill_tick_p99_ms": 0.0,
         "handoffs": 0, "handoff_bytes": 0,
         "routing": dict(colo.routing)},
        {"mode": "disagg", "completed": drep["completed"],
         "tick_p99_ms": dis.tick_latency_ms("decode"),
         "prefill_tick_p99_ms": dis.tick_latency_ms("prefill"),
         "handoffs": dis.handoffs, "handoff_bytes": dis.handoff_bytes,
         "routing": dict(dis.routing)},
    ]
    c_p99, d_p99 = results[0]["tick_p99_ms"], results[1]["tick_p99_ms"]
    beats = 0.0 < d_p99 < c_p99
    common.emit("disagg_tick", d_p99 * 1e3,
                f"{d_p99:.2f}v{c_p99:.2f}ms,"
                f"{dis.handoffs}handoffs,"
                f"{'WIN' if beats else 'LOSS'}")
    return {
        "bench": "disagg",
        "n_devices": jax.device_count(),
        "n_slices": n_slices,
        "roles": {"prefill": list(plan.prefill),
                  "decode": list(plan.decode)},
        "n_requests": len(arrivals),
        "block_size": block_size,
        "results": results,
        "disagg_beats_colocated": beats,
    }


def cascade_series(cfg, params, *, block_size: int, smoke: bool) -> dict:
    """Flat in-place tick vs cascade tick over shared-prefix lane groups.

    Each cell inserts ``lanes`` prompts that share a ``prefix_blocks``-deep
    radix prefix (the pool dedups it to one refcounted chain) plus a
    16-token distinct tail, then times *live* decode ticks — live, because
    frozen at-capacity lanes drop out of grouping and the cascade tick
    would degrade to the flat executable, timing nothing.  The tail length
    and tick count are chosen so every lane stays inside one pow2 suffix
    bucket: the whole run re-invokes a single jitted executable per
    backend.  Alongside wall time the cell records the grouping stats
    (``cascade_stats``) and the dataflow bytes proxy; those carry the
    structural O(prefix) claim, which holds regardless of the platform's
    wall-clock crossover.
    """
    lanes_list = (2, 4, 8)
    prefix_list = (8, 32) if smoke else (8, 32, 64)
    iters, tail = 4, 16
    rng = np.random.default_rng(7)
    results = []
    for nbp in prefix_list:
        shared = rng.integers(1, cfg.vocab, size=nbp * block_size,
                              dtype=np.int32)
        for lanes in lanes_list:
            rec = {"lanes": lanes, "prefix_blocks": nbp,
                   "prefix_tokens": nbp * block_size,
                   "block_size": block_size}
            for mode, backend in (("inplace", "xla"),
                                  ("cascade", "cascade")):
                ad = make_adapter(cfg, params, n_slots=lanes,
                                  max_len=nbp * block_size + 48,
                                  paged=True, block_size=block_size,
                                  backend=backend)
                for slot in range(lanes):
                    suffix = rng.integers(1, cfg.vocab, size=tail,
                                          dtype=np.int32)
                    ad.insert(slot, np.concatenate([shared, suffix]),
                              max_new=24)
                toks = np.zeros(lanes, np.int32)
                active = np.ones(lanes, bool)
                for _ in range(2):        # compile + settle suffix bucket
                    ad.decode(toks, active)
                if backend == "cascade":
                    if ad.last_groups < 1:
                        raise SystemExit(
                            f"cascade cell lanes={lanes} nbp={nbp}: prefix "
                            f"sharing did not form a group — the series "
                            f"would time the flat degrade path")
                    rec.update(ad.cascade_stats())
                rec[f"{mode}_bytes_proxy"] = ad.tick_bytes_proxy()[mode]
                dt = np.inf
                for _ in range(3):        # best-of-3: see decode_tick
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        ad.decode(toks, active)   # live, host-synced
                    dt = min(dt, time.perf_counter() - t0)
                rec[f"{mode}_tok_s"] = lanes * iters / max(dt, 1e-9)
            rec["speedup"] = rec["cascade_tok_s"] / max(
                rec["inplace_tok_s"], 1e-9)
            common.emit(f"cascade_P{nbp}_L{lanes}",
                        1e6 * lanes / rec["cascade_tok_s"],
                        f"{rec['speedup']:.2f}x_vs_flat,"
                        f"{rec['prefix_rows']}v{rec['prefix_rows_flat']}"
                        f"prefix_rows")
            results.append(rec)
    deep = max(results,
               key=lambda r: (r["prefix_blocks"], r["lanes"]))
    return {
        "bench": "cascade",
        "block_size": block_size,
        "results": results,
        "cascade_beats_flat_deep": deep["speedup"] >= 1.0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--budget-slots", type=int, default=4,
                    help="HBM budget expressed in dense max_len slots")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: minimal sizes, same schema")
    ap.add_argument("--sharded", action="store_true",
                    help="add the sharded_tick series (1 vs N virtual "
                         "devices; run under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--disagg", action="store_true",
                    help="run the disaggregated prefill/decode series "
                         "instead of the kvcache bench and write its own "
                         "payload (BENCH_disagg.json by default); run "
                         "under XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8")
    ap.add_argument("--cascade", action="store_true",
                    help="run the shared-prefix cascade-vs-flat decode "
                         "tick series instead of the kvcache bench and "
                         "write its own payload (BENCH_cascade.json by "
                         "default)")
    ap.add_argument("--expect-devices", type=int, default=0,
                    help="fail fast unless jax sees at least this many "
                         "devices (the sharded CI job passes 8 so a "
                         "silently ineffective XLA_FLAGS cannot degrade "
                         "the series to a vacuous 1-slice run)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    enable_compile_cache()
    if not args.out:
        args.out = str(pathlib.Path(__file__).parent /
                       ("BENCH_disagg.json" if args.disagg
                        else "BENCH_cascade.json" if args.cascade
                        else "BENCH_kvcache.json"))
    if args.smoke:
        args.requests, args.max_len, args.budget_slots = 8, 32, 2
    if args.expect_devices and jax.device_count() < args.expect_devices:
        raise SystemExit(
            f"expected >= {args.expect_devices} devices, jax sees "
            f"{jax.device_count()} — is XLA_FLAGS="
            f"--xla_force_host_platform_device_count set?")

    cfg = dataclasses.replace(configs.smoke_config(args.arch),
                              param_dtype="float32")
    params, _ = lm.init(jax.random.key(0), cfg, {})
    if args.disagg:
        payload = disagg_series(cfg, params, block_size=args.block_size)
        payload["arch"] = args.arch
        common.emit_json(args.out, payload)
        if not payload["disagg_beats_colocated"]:
            print("WARNING: disagg decode ticks did not beat the "
                  "colocated gateway under the prefill burst")
        return
    if args.cascade:
        payload = cascade_series(cfg, params, block_size=args.block_size,
                                 smoke=args.smoke)
        payload["arch"] = args.arch
        common.emit_json(args.out, payload)
        if not payload["cascade_beats_flat_deep"]:
            print("WARNING: cascade tick did not beat the flat tick at "
                  "the deepest shared-prefix cell")
        return
    arrivals = make_trace(cfg, args.requests, args.max_len, args.max_new)
    warm_lens = tuple(sorted({len(a.payload) for a in arrivals}))
    budget_bytes = args.budget_slots * kv_bytes_per_slot(cfg, args.max_len)

    results = []
    for layout in ("dense", "paged"):
        rec = run_layout(layout, cfg, params, arrivals,
                         max_len=args.max_len, n_new=args.max_new,
                         budget_bytes=budget_bytes,
                         block_size=args.block_size, warm_lens=warm_lens)
        results.append(rec)
        common.emit(
            f"kvcache_{layout}", rec["p99_latency_ms"] * 1e3,
            f"{rec['max_concurrent_slots']}slots,"
            f"{rec['completed']}done,{rec['dropped']}drop")
    dense, paged = results
    # n_slots large enough that the per-call compute dominates dispatch
    # overhead — at 4 slots the smoke-size ticks are overhead-bound and
    # the gather-vs-inplace ratio loses its discriminating power
    ticks = decode_tick_series(
        cfg, params, block_size=args.block_size,
        n_slots=12 if args.smoke else 16, nb_list=(2, 4, 8),
        iters=25 if args.smoke else 60)
    payload = {
        "bench": "kvcache",
        "arch": args.arch,
        "budget_bytes": budget_bytes,
        "max_len": args.max_len,
        "block_size": args.block_size,
        "results": results,
        "paged_gt_dense": (paged["max_concurrent_slots"]
                           > dense["max_concurrent_slots"]),
        "decode_tick": ticks,
    }
    if args.sharded:
        payload["sharded_tick"] = sharded_tick_series(
            cfg, params, block_size=args.block_size)
    common.emit_json(args.out, payload)
    if not payload["paged_gt_dense"]:
        print("WARNING: paged did not beat dense concurrency at this budget")


if __name__ == "__main__":
    main()
