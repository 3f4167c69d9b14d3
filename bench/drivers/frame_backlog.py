"""Front door of the frame cells: the paper's micro-batching gateway,
driven at capacity.

``MicroBatchGateway.run`` is called back to back, each call on a chunk of
``chunk`` frames that are all due (at most ``GatewayConfig.max_queue``, a
multiple of the largest bucket), so nothing is dropped and every batch
fills the largest bucket.  The wall clock is around the calls: the window
runs from its opening to the end of the last call that started inside
it, and ``frames_per_s`` is the frames classified over that time.

``correct``: a sample of the window's frames, drawn from the seed, is
judged by the plain reference (``bench/reference/lenet_sc.py``) on two
numbers, each within the configuration's limit of that name:
``max_logit_gap``, the widest gap by which the reference's logit of the
served class lies below the reference's best (an SC feature that differs
moves the class), and ``max_logit_rel_err``, the widest relative L2
error of a frame's served logits, as the tail program returned them on
the timed path, against the reference's (the tail's precision).  With
``r.control`` the named control of the reference takes the program's
place in both, and the program's own readings go to ``info``.
"""
from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from bench import frames, traffic, weights


def run(r) -> dict:
    import jax
    from repro.models.lenet import LeNetConfig
    from repro.serve.gateway import frontend as fe
    from repro.serve.gateway.gateway import GatewayConfig, MicroBatchGateway
    from repro.serve.gateway.sensors import Arrival

    mix, c, sc = r.mix, r.config["lenet"], r.config["sc"]
    if mix["kind"] != "frames":
        raise ValueError(f"a frame cell needs a frames mix, not "
                         f"{mix['kind']!r}")
    params = weights.lenet_params(c, r.seed)
    spec = fe.FrontendSpec(mode=r.config["frontend"]["mode"],
                           bits=sc["bits"],
                           sc_impl=r.config["frontend"]["sc_impl"],
                           lenet=LeNetConfig(**c))
    have = spec.sc_cfg
    for key in ("scheme", "adder", "s0_mode", "weight_scale",
                "soft_threshold"):
        if getattr(have, key) != sc[key]:
            raise ValueError(f"the program's SC layer has {key}="
                             f"{getattr(have, key)!r}, the configuration "
                             f"{sc[key]!r}")
    gcfg = dict(r.config["gateway"])
    gcfg["bucket_sizes"] = tuple(gcfg["bucket_sizes"])
    gw = MicroBatchGateway(GatewayConfig(**gcfg), spec, params=params)
    chunk, top = int(mix["chunk"]), gcfg["bucket_sizes"][-1]
    if chunk > gcfg["max_queue"] or chunk % top:
        raise ValueError(f"chunk {chunk} must be a multiple of {top} "
                         f"within max_queue {gcfg['max_queue']}")
    images = frames.pool(r.seed, int(mix["pool"]))
    n_lists = int(mix["chunks"])
    index = traffic.frame_indices(mix, r.seed, n_lists * chunk).reshape(
        n_lists, chunk)
    lists = [[Arrival(uid=j, t=0.0, endpoint=j, kind="frame",
                      payload=images[index[k, j]]) for j in range(chunk)]
             for k in range(n_lists)]
    gw.run(lists[0])                          # compiles the full bucket
    jitted = gw.jit_fns()

    def compile_counts():
        return {name: fn._cache_size() for name, fn in jitted.items()}
    compiled = compile_counts()
    served_logits: list = []            # the tail's output, batch by batch
    tail = gw._gateway_fns[top]

    def kept(p, x):
        out = tail(p, x)
        served_logits.append(out)
        return out
    gw._gateway_fns[top] = kept
    if r.trace:
        def annotated(name, fn):
            def call(p, x):
                with jax.profiler.TraceAnnotation(name):
                    return jax.block_until_ready(fn(p, x))
            return call
        gw._sensor_fns[top] = annotated("bench:sensor_stage",
                                        gw._sensor_fns[top])
        gw._gateway_fns[top] = annotated("bench:gateway_stage",
                                         gw._gateway_fns[top])
    r.setup_done()

    outputs: list[np.ndarray] = []
    failed = 0
    trace_s = min(float(mix.get("trace_s", 4.0)), r.seconds)
    t_trace = 1.0 if r.seconds - trace_s >= 1.0 else 0.0
    tracing = traced = False
    traced_calls = 0
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if r.trace \
        else (lambda name: contextlib.nullcontext())
    t_open = time.perf_counter()
    t_close = t_open + r.seconds
    now = t_open
    while now < t_close:
        if r.trace and not traced and now - t_open >= t_trace:
            r.trace_begin()
            tracing = traced = True
            t_trace_end = time.perf_counter() + trace_s
        with span("bench:run_call"):
            tel = gw.run(lists[len(outputs) % n_lists])
        with span("bench:collect"):
            out = np.full(chunk, -1, np.int64)
            for rec in tel.records:
                out[rec.uid] = rec.output
            failed += len(tel.dropped) + int(np.sum(out < 0))
            outputs.append(out)
        now = time.perf_counter()
        if tracing:
            traced_calls += 1
            if now >= t_trace_end:
                r.trace_end()
                tracing = False
    if tracing:
        r.trace_end()
    window_s = now - t_open
    recompiled = compile_counts() != compiled
    memory = r.memory_peak()
    n_frames = len(outputs) * chunk
    r.log(f"frames: {n_frames} in {len(outputs)} calls of {chunk} over "
          f"{window_s:.3f} s, {failed} failed; jit entries "
          f"{compile_counts()} (changed in the window: {recompiled}); "
          f"device peak_bytes_in_use {memory}")
    counters = {"frames": traced_calls * chunk, "lenet": c}

    want = min(int(mix["check_frames"]), n_frames)
    pick = traffic.rng_for(r.seed, 9).choice(n_frames, want, replace=False)
    calls, pos = pick // chunk, pick % chunk
    if len(served_logits) != len(outputs) * (chunk // top):
        raise RuntimeError(f"{len(served_logits)} tail batches for "
                           f"{len(outputs)} calls of {chunk} frames")
    logits = np.concatenate([np.asarray(a) for a in served_logits])[pick]
    served = np.asarray([outputs[k][j] for k, j in zip(calls, pos)])
    shown = np.asarray([index[k % n_lists, j] for k, j in zip(calls, pos)])
    del gw, params, lists, outputs, served_logits, tail
    gc.collect()
    t = time.perf_counter()
    res = r.reference().class_logits(c, sc, r.seed, images,
                                      control=r.control)
    ref = res["logits"][shown]
    limits = r.config["limits"]

    def numbers(cls, got):
        best = ref.max(-1)
        bad = cls < 0
        gap = np.where(bad, np.inf,
                       best - ref[np.arange(want), np.where(bad, 0, cls)])
        err = np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
        widest = (float(np.max(gap)), float(np.max(err))) if want \
            else (float("inf"),) * 2
        return [("max_logit_gap", widest[0], float(limits["max_logit_gap"])),
                ("max_logit_rel_err", widest[1],
                 float(limits["max_logit_rel_err"]))]
    checks = numbers(served, logits)
    r.log(f"reference: {want} frames of {len(images)} images compared in "
          f"{time.perf_counter() - t:.1f} s; {checks}, classes differing "
          f"{int(np.sum(served != ref.argmax(-1)))}")
    info = {"recompiled_in_window": recompiled, "frames_compared": want}
    if r.control:
        info["program"] = {name: value for name, value, _ in checks}
        got = res["control"][shown]
        checks = numbers(got.argmax(-1), got)
        r.log(f"control {r.control} in the program's place: {checks}")
    return {"attempted": n_frames, "failed": failed,
            "e2e": {"frames_per_s": n_frames / window_s},
            "counters": counters, "memory_peak_bytes": memory,
            "checks": checks, "info": info}
