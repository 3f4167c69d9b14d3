"""Warm-vs-cold admission latency under prefix-hit chunked prefill.

The serving analogue of the paper's economy: work moved out of the
expensive domain is work you stop paying for.  For a fleet of sensors
sharing one system prompt, admission cost should fall with the shared
prefix length — a warm insert gathers the shared blocks from the arena and
folds prefill only over the remaining suffix chunks.

Per shared-block count H the bench builds prompts ``prefix(H*bs) + tail``
and measures, post-compile (median over --repeats):

  cold_ms   insert with no usable prefix in the radix index
  warm_ms   insert after a sibling seeded the same H-block prefix

The acceptance trend (gated by ``benchmarks/check_bench.py`` in CI) is
``warm_ms < cold_ms`` for every H >= 2 — admission latency must actually
drop once a meaningful prefix is shared, at equal prompt length.

Run:  PYTHONPATH=src python benchmarks/prefix_prefill_bench.py
      [--arch stablelm_3b] [--block-size 8] [--tail 8] [--repeats 5]
      [--smoke]
"""
import argparse
import dataclasses
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import common  # noqa: E402
import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serve.gateway.slots import make_adapter  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def time_insert(ad, mk_prompt, max_new, repeats, want_skip):
    """Median wall-clock of ``insert`` into slot 0 (cleared between runs);
    callers are responsible for having warmed the relevant jit buckets.

    ``mk_prompt`` builds a FRESH prompt per repeat — clearing a slot parks
    its registered blocks in the LRU still indexed, so re-timing the same
    prompt would measure a prefix *hit* from the second repeat on and a
    "cold" series would silently turn warm.  ``want_skip`` asserts each
    repeat really took the intended path (0 = cold, else = tokens skipped).
    """
    times = []
    for _ in range(repeats):
        prompt = mk_prompt()
        t0 = time.perf_counter()
        ad.insert(0, prompt, max_new=max_new)
        times.append((time.perf_counter() - t0) * 1e3)
        skipped = ad.slot_stats(0)["prefill_tokens_skipped"]
        assert skipped == want_skip, (skipped, want_skip)
        ad.clear(0)
    return statistics.median(times)


def run_point(cfg, params, H, bs, tail, max_new, repeats, seed):
    """One (shared_blocks=H) measurement; a fresh adapter per point so the
    radix index holds exactly what the scenario says it holds."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, size=H * bs, dtype=np.int32)
    mk_tail = lambda: rng.integers(0, cfg.vocab, size=tail, dtype=np.int32)
    P = H * bs + tail
    max_len = -(-(P + max_new) // bs) * bs + bs
    ad = make_adapter(cfg, params, n_slots=2, max_len=max_len,
                      paged=True, block_size=bs,
                      num_blocks=8 * (P + max_new) // bs + 8)

    mk_cold = lambda: np.concatenate(
        [rng.integers(0, cfg.vocab, size=H * bs, dtype=np.int32), mk_tail()])
    mk_warm = lambda: np.concatenate([prefix, mk_tail()])

    # compile every bucket the measurements will touch: a cold fold of this
    # length, then a warm (resumed) fold
    ad.insert(0, mk_cold(), max_new=max_new)
    ad.clear(0)
    ad.insert(0, mk_warm(), max_new=max_new)
    ad.clear(0)

    skipped = H * bs
    # cold: every repeat is a FRESH random prompt, so nothing in the radix
    # index matches and the whole prompt folds
    cold_ms = time_insert(ad, mk_cold, max_new, repeats, want_skip=0)
    # warm: the seeded H-block prefix hits; only the tail chunks fold
    warm_ms = time_insert(ad, mk_warm, max_new, repeats, want_skip=skipped)
    return {
        "shared_blocks": H,
        "prompt_len": P,
        "suffix_len": P - skipped,
        "prefill_tokens_skipped": skipped,
        "cold_ms": cold_ms,
        "warm_ms": warm_ms,
        "speedup": cold_ms / warm_ms if warm_ms else 0.0,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_3b")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--tail", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--shared", type=int, nargs="+",
                    default=[0, 2, 4, 8])
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: fewer points/repeats, same schema")
    ap.add_argument("--out", default=str(pathlib.Path(__file__).parent
                                         / "BENCH_prefix.json"))
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.shared, args.repeats = [0, 2, 4], 3

    cfg = dataclasses.replace(configs.smoke_config(args.arch),
                              param_dtype="float32")
    params, _ = lm.init(jax.random.key(0), cfg, {})

    results = []
    for H in args.shared:
        rec = run_point(cfg, params, H, args.block_size, args.tail,
                        args.max_new, args.repeats, seed=10 + H)
        results.append(rec)
        common.emit(f"prefix_H{H}", rec["warm_ms"] * 1e3,
                    f"cold={rec['cold_ms']:.2f}ms,"
                    f"skip={rec['prefill_tokens_skipped']}tok")
    payload = {
        "bench": "prefix",
        "arch": args.arch,
        "block_size": args.block_size,
        "results": results,
        "warm_beats_cold": all(r["warm_ms"] < r["cold_ms"]
                               for r in results if r["shared_blocks"] >= 2),
    }
    common.emit_json(args.out, payload)
    if not payload["warm_beats_cold"]:
        print("WARNING: warm admission did not beat cold at >=2 shared "
              "blocks")


if __name__ == "__main__":
    main()
