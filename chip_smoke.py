"""Bring-up check: drive the serving stack's main paths once on a TPU.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --four-chips          # one host with four chips

One chip runs two phases in this one process (a chip belongs to one
process at a time):

  LM     stablelm-3b at its published width (32 layers, d_model 2560, 32
         heads of 80, d_ff 6912, vocab 50304; random bf16 weights from
         --seed) through ServeSpec -> make_gateway -> warmup -> run on the
         paged gateway with the Pallas decode kernel.  Checks: every request
         completes with its max_new_tokens; a shared two-block prefix is
         resumed, not recomputed; the compiled decode tick holds the Mosaic
         kernel; from one live state, the Pallas tick's logits and written
         K/V rows agree with the XLA reference tick's, and so does the
         gateway's own donated tick.
  frame  the paper's deployment: a seeded sensor fleet through the
         micro-batching gateway, SC and binary frontends, LeNet-5 at its
         published width.  Checks: every frame completes, no bucket
         compiles during the run, and the Pallas SC datapath (sng_pack +
         sc_dot) equals the table path bit for bit at LeNet's first layer.

--four-chips runs only the paths that exist across chips: the sharded
gateway over four one-device slices (affinity routing, one migration at
least) and a one-prefill/three-decode disaggregated gateway (handoffs),
each checked for one device per slice and for greedy tokens equal to the
colocated one-slice gateway's.

Any failed check raises: the script exits non-zero and prints no result.
The last stdout line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Seconds, token counts and bytes printed before it are information, not
measurements of speed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402

# Pallas vs XLA decode tick from one state, as ||got - want|| / ||want||
# over the live lanes.  Both read bf16 K/V and accumulate in f32, but the
# XLA reference rounds the softmax probabilities to bf16 (relative step
# 2**-8) before the value product and the kernel does not; that rounding
# is carried through 32 residual layers of bf16 activations.  A 32-layer
# bf16 model of the same family (d_model 160) drifts 1.5e-2 on the CPU;
# the bound is about three times that.  A kernel that reads a wrong block
# or masks a wrong position moves the logits by O(1).
TICK_RTOL = 5e-2


class SmokeFailure(RuntimeError):
    """A check of the bring-up failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def device_check(chips: int) -> dict:
    """The device JAX reports; fail unless it is a TPU that runs the Pallas
    kernels through Mosaic (never the interpreter) with ``chips`` chips."""
    from repro.kernels.ops import default_interpret
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"no TPU: JAX runs on {d.platform!r}")
    check(not default_interpret(),
          "Pallas kernels would run in interpret mode on the TPU "
          "(REPRO_KERNELS_INTERPRET is set)")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def init_lm_params(cfg, seed: int):
    """Random weights of ``cfg`` in its parameter dtype, made on the device
    in one program (no float32 copy of the whole model is kept)."""
    from repro.models import lm
    params = jax.jit(lambda k: lm.init(k, cfg)[0])(jax.random.key(seed))
    return jax.block_until_ready(params)


def lm_prompts(vocab: int, bs: int, seed: int) -> list[np.ndarray]:
    """Eight prompts of 2-4 blocks; the first three share a 2-block prefix.
    Three lengths only: every new chunk offset is one more fold compile."""
    rng = np.random.default_rng(seed)

    def tok(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    shared = tok(2 * bs)
    return [np.concatenate([shared, tok(bs)]),
            np.concatenate([shared, tok(bs)]),
            np.concatenate([shared, tok(2 * bs)]),
            tok(2 * bs), tok(3 * bs), tok(4 * bs), tok(2 * bs), tok(4 * bs)]


def _tick_fn(cfg, backend: str):
    """One in-place decode tick under ``backend`` that donates nothing, so
    it can run from the adapter's live state: returns the logits and the
    K/V rows the tick wrote (at each lane's block and offset)."""
    from repro.serve import engine

    def tick(params, arena, dense, tables, tokens, wbids):
        new_arena, _, logits = engine.decode_step_paged(
            cfg, params, dense, tokens, tables=tables, lens=dense["len"],
            arena=arena, wbids=wbids, backend=backend)
        offs = dense["len"] % arena["k"].shape[-3]
        return (logits, new_arena["k"][:, wbids, 0, offs],
                new_arena["v"][:, wbids, 0, offs])
    return jax.jit(tick)


def _write_targets(adapter, active: np.ndarray) -> np.ndarray:
    """The arena block each live lane's next row lands in (the trash block
    elsewhere) — what ``PagedKVSlotAdapter.decode`` computes."""
    wbids = np.zeros(adapter.n_slots, np.int32)
    for s in np.nonzero(active)[0]:
        wbids[s] = adapter.tables[s, int(adapter.lens[s]) // adapter.bs]
    return wbids


def lm_phase(cfg, *, seed: int, n_slots: int = 8, max_len: int = 512,
             block_size: int = 16, max_new_tokens: int = 16) -> dict:
    """The paged LM gateway with the Pallas tick, end to end (see the
    module docstring).  Returns what it saw; raises on a failed check."""
    from repro.serve.gateway.sensors import Arrival
    from repro.serve.gateway.slots import Request
    from repro.serve.spec import ServeSpec, make_gateway

    t0 = time.perf_counter()
    params = init_lm_params(cfg, seed)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    log("lm", f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{n_params} params ({cfg.param_dtype}), made in "
        f"{time.perf_counter() - t0:.1f} s")
    spec = ServeSpec(n_slots=n_slots, max_len=max_len, paged=True,
                     block_size=block_size, backend="pallas",
                     max_new_tokens=max_new_tokens)
    gw = make_gateway(cfg, params, spec)
    ad = gw.batcher.adapter
    check(ad.backend == "pallas",
          f"the gateway resolved backend {ad.backend!r}, not 'pallas'")
    log("lm", f"arena: {ad.arena['k'].shape[1]} blocks of {block_size} "
        f"tokens, {ad._token_bytes} B/token")

    prompts = lm_prompts(cfg.vocab, block_size, seed)
    t0 = time.perf_counter()
    gw.warmup(tuple(sorted({len(p) for p in prompts})))
    log("lm", f"warmup (compiles) {time.perf_counter() - t0:.1f} s")
    arrivals = [Arrival(uid=i, t=1e-3 * i, endpoint=i, kind="prompt",
                        payload=p) for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    tel = gw.run(arrivals)
    recs = [r for r in tel.records if r.kind == "prompt"]
    skipped = sum(r.prefill_tokens_skipped for r in recs)
    log("lm", f"served {len(recs)} requests, "
        f"{sum(r.tokens_out for r in recs)} tokens out, "
        f"{skipped} prompt tokens skipped, "
        f"{time.perf_counter() - t0:.1f} s wall")
    check(not tel.dropped, f"requests dropped: {tel.dropped}")
    check(sorted(r.uid for r in recs) == list(range(len(prompts))),
          "not every request completed")
    check(all(r.tokens_out == max_new_tokens for r in recs),
          f"tokens out {[r.tokens_out for r in recs]}, want "
          f"{max_new_tokens} each")
    check(skipped > 0, "no prefix-hit resume ran (prefill_tokens_skipped 0)")

    # one tick from a live state: admit without decoding, then run the
    # Pallas and XLA ticks side by side, then the gateway's own tick
    b = gw.batcher
    for i, p in enumerate(prompts[:n_slots]):
        b.submit(Request(uid=100 + i, prompt=p,
                         max_new_tokens=max_new_tokens))
    b.step(decode=False)
    active = np.asarray([r is not None for r in b.active])
    check(active.any(), "no lane admitted for the tick comparison")
    wbids = _write_targets(ad, active)
    offs = np.asarray(ad.lens, np.int32) % ad.bs
    args = (ad.params, ad.arena, ad.cache, jnp.asarray(ad.tables),
            jnp.asarray(b.last_token)[:, None], jnp.asarray(wbids))
    lg_p, kp, vp = _tick_fn(cfg, "pallas")(*args)
    lg_x, kx, vx = _tick_fn(cfg, "xla")(*args)
    e_logits = rel_err(lg_p[active], lg_x[active])
    e_rows = max(rel_err(kp[:, active], kx[:, active]),
                 rel_err(vp[:, active], vx[:, active]))
    b.step()                      # the adapter's donated Pallas tick
    kl = ad.arena["k"][:, wbids, 0, offs]
    vl = ad.arena["v"][:, wbids, 0, offs]
    e_live = max(rel_err(ad.last_logits[active], lg_x[active]),
                 rel_err(kl[:, active], kx[:, active]),
                 rel_err(vl[:, active], vx[:, active]))
    agree = float(np.mean(np.argmax(np.asarray(lg_p[active]), -1)
                          == np.argmax(np.asarray(lg_x[active]), -1)))
    log("lm", f"pallas vs xla tick on {int(active.sum())} lanes: logits "
        f"rel err {e_logits:.3e}, K/V rows {e_rows:.3e}, gateway tick "
        f"{e_live:.3e} (bound {TICK_RTOL:g}); "
        f"argmax agreement {agree:.3f}")
    check(e_logits <= TICK_RTOL, f"logits rel err {e_logits:.3e}")
    check(e_rows <= TICK_RTOL, f"K/V rows rel err {e_rows:.3e}")
    check(e_live <= TICK_RTOL, f"gateway tick rel err {e_live:.3e}")
    b.run()

    tick = ad._decode.lower(
        ad.params, ad.arena, ad.cache, jnp.asarray(ad.tables),
        jnp.zeros((n_slots, 1), jnp.int32), jnp.ones((n_slots,), bool),
        jnp.zeros((n_slots,), jnp.int32)).compile()
    kernel = "tpu_custom_call" in tick.as_text()
    log("lm", f"compiled decode tick holds tpu_custom_call: {kernel}")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log("lm", f"device peak_bytes_in_use {stats['peak_bytes_in_use']}")
    return {"completed": len(recs), "prefill_tokens_skipped": skipped,
            "logits_rel_err": e_logits, "rows_rel_err": e_rows,
            "live_rel_err": e_live, "tick_has_kernel": kernel}


def frame_phase(lenet_cfg, *, seed: int, n_endpoints: int = 16,
                duration_s: float = 2.0, sc_frames: int = 32,
                sc_bits: tuple[int, ...] = (5, 8)) -> dict:
    """The paper's frame path on both frontends, and the Pallas SC
    datapath against the table path (see the module docstring)."""
    from repro.core import sc_layer
    from repro.kernels import ops
    from repro.models import lenet
    from repro.serve.gateway import frontend as fe
    from repro.serve.gateway.gateway import GatewayConfig, MicroBatchGateway
    from repro.serve.gateway.sensors import FleetConfig, SensorFleet

    arrivals = SensorFleet(FleetConfig(n_endpoints=n_endpoints,
                                       seed=seed)).events(duration_s)
    params = lenet.init(jax.random.key(seed), lenet_cfg)
    out: dict = {}
    for mode in ("sc", "binary"):
        gw = MicroBatchGateway(GatewayConfig(),
                               fe.FrontendSpec(mode=mode, lenet=lenet_cfg),
                               params=params)
        t0 = time.perf_counter()
        gw.warmup()
        compile_s = time.perf_counter() - t0
        before = gw.compile_counts()
        tel = gw.run(arrivals)
        after = gw.compile_counts()
        frames = [r for r in tel.records if r.kind == "frame"]
        log("frame", f"{mode}: {len(frames)}/{len(arrivals)} frames, "
            f"{len(tel.dropped)} dropped, warmup (compiles) "
            f"{compile_s:.1f} s, jit entries per bucket {after}")
        check(after == before,
              f"{mode}: compiles during the run {before} -> {after}")
        check(not tel.dropped and len(frames) == len(arrivals),
              f"{mode}: {len(frames)} of {len(arrivals)} frames completed")
        check(all(0 <= r.output < lenet_cfg.classes for r in frames),
              f"{mode}: a prediction outside the classes")
        out[mode] = len(frames)

    # the SC first layer as the paper's datapath: levels -> comparator SNG
    # -> packed AND/popcount -> TFF tree, both weight banks fused
    x = np.stack([a.payload for a in arrivals[:sc_frames]])
    patches = sc_layer.extract_patches(
        jnp.asarray(x, jnp.float32) / 255.0, lenet_cfg.ksize)
    K = patches.shape[-1]
    w = params["conv1"]["w"].reshape(K, lenet_cfg.conv1_filters)
    for bits in sc_bits:
        cfg = sc_layer.SCConfig(bits=bits, adder="tff")
        x_lvl = sc_layer.quantize_levels(patches, bits).reshape(-1, K)
        pos, neg, _ = sc_layer.quantize_weights(w, bits, cfg.weight_scale)
        w_lvl = jnp.concatenate([pos, neg], axis=1)
        got = np.asarray(ops.sc_dot_from_levels(x_lvl, w_lvl, bits))
        want = np.asarray(sc_layer.counts_via_table(x_lvl, w_lvl, cfg))
        log("frame", f"Pallas SC datapath at {bits} bits, "
            f"{x_lvl.shape[0]}x{K} levels x {w_lvl.shape[1]} kernels: "
            f"{int(np.sum(got != want))} counts differ from the table path")
        check(np.array_equal(got, want),
              f"SC kernels differ from the table path at {bits} bits")
    return out


def four_chip_phase(cfg, *, seed: int, max_len: int = 256,
                    block_size: int = 16, max_new_tokens: int = 8) -> dict:
    """Sharded and disaggregated gateways over four one-device slices,
    against the colocated one-slice gateway (see the module docstring).
    One lane per slice: a second request on the owning slice then blocks,
    which is what makes the router migrate."""
    from repro.dist.sharding import slice_meshes
    from repro.launch.mesh import make_serving_mesh
    from repro.serve.gateway.slots import Request
    from repro.serve.shard import RolePlan
    from repro.serve.spec import ServeSpec, make_gateway

    params = init_lm_params(cfg, seed)
    subs = slice_meshes(make_serving_mesh(4, 1))
    spec = ServeSpec(n_slots=1, max_len=max_len, paged=True,
                     block_size=block_size, backend="pallas",
                     max_new_tokens=max_new_tokens)
    prompts = lm_prompts(cfg.vocab, block_size, seed)[:6]
    # r0 opens the shared chain; r1 shares it and arrives while r0
    # decodes (affinity, then a migration); the rest spread by load
    waves = [[0], [1], [2, 3, 4, 5]]

    def serve(submit, step, busy):
        reqs = {}
        for wave in waves:
            for uid in wave:
                reqs[uid] = Request(uid=uid, prompt=prompts[uid],
                                    max_new_tokens=max_new_tokens)
                submit(reqs[uid])
            step()
        while busy():
            step()
        return {uid: list(r.generated) for uid, r in reqs.items()}

    def placement(gw) -> list:
        devs = []
        for sl in gw.slices:
            want = set(sl.mesh.devices.flat)
            for name, tree in (("arena", sl.adapter.arena),
                               ("params", sl.adapter.params)):
                got = {d for a in jax.tree.leaves(tree) for d in a.devices()}
                check(got == want and len(want) == 1,
                      f"slice {sl.idx} {name} on {got}, mesh {want}")
            devs.append(next(iter(want)))
        check(len(set(devs)) == len(devs), f"slices share devices: {devs}")
        return devs

    out: dict = {}
    t0 = time.perf_counter()
    gw = make_gateway(cfg, params, spec, mesh=subs)
    devs = placement(gw)
    sharded = serve(gw.submit, gw.step, lambda: gw.busy)
    out["sharded"] = {"devices": [str(d) for d in devs],
                      "routing": dict(gw.routing),
                      "migrations": gw.migrations}
    log("4chip", f"sharded: slices on {out['sharded']['devices']}, routing "
        f"{gw.routing}, migrations {gw.migrations}, "
        f"{time.perf_counter() - t0:.1f} s")
    check(gw.routing["affinity"] >= 1, "no request was routed by affinity")
    check(gw.migrations >= 1, "the router migrated nothing")
    del gw

    t0 = time.perf_counter()
    gw = make_gateway(cfg, params, spec, mesh=subs,
                      roles=RolePlan.split(1, 3))
    placement(gw)
    disagg = serve(gw.submit, gw.step, lambda: gw.busy)
    out["disagg"] = {"handoffs": gw.handoffs}
    log("4chip", f"disaggregated 1+3: handoffs {gw.handoffs}, "
        f"{time.perf_counter() - t0:.1f} s")
    check(gw.handoffs > 0, "no prefill->decode handoff happened")
    del gw

    gw = make_gateway(cfg, params, spec)
    colocated = serve(gw.batcher.submit, gw.batcher.step,
                      lambda: gw.batcher.busy)
    for name, got in (("sharded", sharded), ("disagg", disagg)):
        same = sum(got[u] == colocated[u] for u in colocated)
        log("4chip", f"{name}: {same}/{len(colocated)} requests' greedy "
            f"tokens equal the colocated gateway's")
        check(same == len(colocated), f"{name} tokens differ from colocated")
        check(all(len(t) == max_new_tokens for t in got.values()),
              f"{name}: a request stopped short")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded and disaggregated gateways, "
                         "on one host with four chips")
    args = ap.parse_args(argv)
    chips = 4 if args.four_chips else 1
    device = device_check(chips)
    log("device", json.dumps(device))
    from repro.launch.compile_cache import enable_compile_cache
    log("device", f"compile cache {enable_compile_cache()}")
    cfg = configs.config("stablelm-3b")
    if args.four_chips:
        four_chip_phase(cfg, seed=args.seed)
    else:
        lm = lm_phase(cfg, seed=args.seed)
        check(lm["tick_has_kernel"],
              "the compiled decode tick holds no tpu_custom_call")
        frame_phase(configs.get("lenet5").config(), seed=args.seed)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
