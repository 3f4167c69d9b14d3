"""Front door of the LM cells: a wall-clock loop over the paged gateway.

The gateway is built as a user builds it, ``make_gateway(cfg, params,
ServeSpec(...))``, and the loop drives its ``ContinuousBatcher``:
``submit`` when a request is due (``perf_counter``), ``step`` in a loop,
and after each step every new token of every live request is stamped on
the host clock (the token is on the host when ``step`` returns: the
adapter copies its argmax back).  ``PromptGateway.run`` is not used: its
clock is virtual and charges only what the program measures of itself.

Arrivals follow the mix: ``backlog`` keeps ``min_pending`` requests
queued at all times; ``poisson`` submits each request at its due time, and
a request's time to first token counts from that due time, so a late loop
shows as latency.  A ramp of ``ramp_s`` seconds before the window fills
the lanes (and the prefix cache); it is neither set-up nor measured.

End-to-end metrics: ``tokens_per_s`` (tokens stamped in the window over
its length), ``tpot_p95_ms`` (every gap between consecutive tokens of a
request whose later token falls in the window) and, for due-time mixes,
``ttft_p90_ms`` (requests due in the window; they are drained after it,
for at most ``drain_cap_s``; one that yields no token has failed).  The
drain also runs until ``check_requests`` requests have finished, so that
a short window still has answers to compare.

``correct``: a sample of finished requests, drawn from the seed with the
longest among them, is replayed through the plain reference
(``bench/reference/decoder.py``): the widest gap by which a served token's
reference logit lies below the reference's best must stay within the
configuration's ``max_logit_gap``.  With ``r.control`` the named control
of the reference takes the program's place: the gap is read of the token
the control puts first at each served position, and the program's own
reading goes to ``info``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import time

import numpy as np

from bench import traffic, weights


def _check_layout(got, want) -> None:
    """The benchmark's weights must have the program's pytree layout."""
    import jax
    if jax.tree.structure(got) != jax.tree.structure(want):
        raise ValueError("the benchmark's weights do not have the "
                         "program's parameter layout")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight {a.shape} {a.dtype} where the "
                             f"program takes {b.shape} {b.dtype}")


def _warm_up(b, mix: dict, vocab: int, seed: int) -> None:
    """One short request per prompt length the mix can produce, behind a
    shared system prompt where the mix has one: every fold chunk, prefix
    gather and the tick compile here, and no other shape."""
    from repro.serve.gateway.slots import Request
    rng = traffic.rng_for(seed, 8)
    n_sys = int(mix["system"]["len"]) if mix.get("system") else 0
    system = rng.integers(0, vocab, n_sys, dtype=np.int32)
    for j, n in enumerate(traffic.lm_prompt_lengths(mix)):
        user = rng.integers(0, vocab, n - n_sys, dtype=np.int32)
        b.submit(Request(uid=-1 - j, prompt=np.concatenate([system, user]),
                         max_new_tokens=2))
    b.run()


class _Recorder:
    """Per-layer counters of the traced part of the window: host time and
    live lengths of each decode tick, host time and prompt tokens of each
    admission, with a host span around each call."""

    def __init__(self, adapter):
        import jax
        self.on = False
        self.ticks: list[tuple[float, list[int]]] = []
        self.prefills: list[tuple[float, int, int]] = []
        span = jax.profiler.TraceAnnotation
        decode, insert = adapter.decode, adapter.insert

        def timed_decode(tokens, active):
            lens = adapter.lens[np.asarray(active, bool)].tolist()
            with span("bench:decode_tick"):
                t = time.perf_counter()
                out = decode(tokens, active)
                dt = time.perf_counter() - t
            if self.on:
                self.ticks.append((dt, lens))
            return out

        def timed_insert(slot, prompt, max_new=None):
            skipped = adapter.prefill_tokens_skipped_total
            with span("bench:prefill"):
                t = time.perf_counter()
                out = insert(slot, prompt, max_new=max_new)
                dt = time.perf_counter() - t
            if self.on:
                self.prefills.append(
                    (dt, len(prompt),
                     adapter.prefill_tokens_skipped_total - skipped))
            return out
        adapter.decode, adapter.insert = timed_decode, timed_insert


def _sample(done: list[dict], seed: int, want_tokens: int,
            max_requests: int) -> list[dict]:
    """Finished requests to replay: the longest, then others in an order
    drawn from the seed, until ``want_tokens`` served tokens."""
    if not done:
        return []
    longest = max(done, key=lambda d: (len(d["req"].generated),
                                       -d["req"].uid))
    rest = [d for d in done if d is not longest]
    order = traffic.rng_for(seed, 7).permutation(len(rest))
    out, n = [longest], len(longest["req"].generated)
    for k in order:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(rest[k])
        n += len(rest[k]["req"].generated)
    return out


def run(r) -> dict:
    import jax
    from repro import configs
    from repro.models import lm
    from repro.serve.gateway.slots import Request
    from repro.serve.spec import ServeSpec, make_gateway

    mix, m = r.mix, r.config["model"]
    if mix["kind"] != "lm":
        raise ValueError(f"an LM cell needs an lm mix, not {mix['kind']!r}")
    cfg = dataclasses.replace(configs.config(r.config["arch"]), **m)
    params = weights.decoder_params(m, r.seed)
    _check_layout(params, lm.init(None, cfg, abstract=True)[0])
    spec = ServeSpec(**r.config["serve"])
    gw = make_gateway(cfg, params, spec)
    b = gw.batcher
    ad = b.adapter
    if getattr(ad, "backend", None) != spec.backend:
        raise ValueError(f"the gateway resolved backend "
                         f"{getattr(ad, 'backend', None)!r}, not "
                         f"{spec.backend!r}")
    n = traffic.lm_request_count(mix, r.seconds, ad.n_slots)
    reqs = traffic.lm_requests(mix, r.seed, cfg.vocab, n)
    _warm_up(b, mix, cfg.vocab, r.seed)
    rec = _Recorder(ad) if r.trace else None
    r.setup_done()

    arrival = mix["arrival"]
    backlog = arrival["process"] == "backlog"
    min_pending = int(arrival.get("min_pending", 0))
    ramp = float(mix.get("ramp_s", 0.0))
    now = time.perf_counter()
    t_open = now + ramp
    t_close = t_open + r.seconds
    trace_s = min(float(mix.get("trace_s", 4.0)), r.seconds)
    t_trace = t_open + min(1.0, r.seconds - trace_s) if r.trace \
        else float("inf")
    tracing = traced = False
    trace_tokens = 0
    live: dict[int, dict] = {}
    done: list[dict] = []
    late: list[float] = []
    i = 0

    def submit(q, t):
        req = Request(uid=q["uid"], prompt=q["prompt"],
                      max_new_tokens=q["max_new"])
        b.submit(req)
        due = t if q["due"] is None else t_open + q["due"]
        live[q["uid"]] = {"req": req, "due": due, "stamps": []}
        late.append(t - due)

    def stamp(finished, t):
        nonlocal trace_tokens
        for req in itertools.chain(b.active, finished):
            if req is None:
                continue
            d = live[req.uid]
            new = len(req.generated) - len(d["stamps"])
            if new:
                d["stamps"].extend([t] * new)
                if tracing:
                    trace_tokens += new
        for req in finished:
            done.append(live.pop(req.uid))

    span = (lambda name: jax.profiler.TraceAnnotation(name)) if r.trace \
        else (lambda name: contextlib.nullcontext())
    lowered0 = None
    while True:
        now = time.perf_counter()
        if lowered0 is None and now >= t_open:
            lowered0 = (r.compiles.lowered, r.compiles.compiled)
            queue_open = len(b.pending)
        if now >= t_close:
            break
        if r.trace and not traced and now >= t_trace:
            r.trace_begin()
            tracing = traced = rec.on = True
            t_trace_end = time.perf_counter() + trace_s
        if tracing and now >= t_trace_end:
            r.trace_end()
            tracing = rec.on = False
        with span("bench:submit"):
            if backlog:
                while len(b.pending) < min_pending and i < n:
                    submit(reqs[i], now)
                    i += 1
            else:
                while i < n and t_open + reqs[i]["due"] <= now:
                    submit(reqs[i], now)
                    i += 1
        if not b.busy:
            nxt = t_open + reqs[i]["due"] if i < n else t_close
            time.sleep(max(0.0, min(nxt, t_close, t_trace_end if tracing
                                    else t_close) - time.perf_counter()))
            continue
        with span("bench:step"):
            finished = b.step()
        stamp(finished, time.perf_counter())
    if tracing:
        r.trace_end()
        tracing = rec.on = False
    if lowered0 is None:
        lowered0 = (r.compiles.lowered, r.compiles.compiled)
        queue_open = len(b.pending)
    queue_close = len(b.pending)
    in_window = (r.compiles.lowered - lowered0[0],
                 r.compiles.compiled - lowered0[1])

    # drain, with no new arrivals: every request due in the window gets
    # its first token, and (a backlog) enough requests finish to compare
    due_in = [q for q in reqs[:n] if q["due"] is not None
              and 0.0 <= q["due"] < r.seconds]
    t_cap = time.perf_counter() + float(mix.get("drain_cap_s", 0.0))
    while time.perf_counter() < t_cap and b.busy:
        while not backlog and i < n and reqs[i]["due"] < r.seconds:
            submit(reqs[i], time.perf_counter())
            i += 1
        if len(done) >= int(mix["check_requests"]) and all(
                live[q["uid"]]["stamps"] if q["uid"] in live else True
                for q in due_in):
            break
        stamp(b.step(), time.perf_counter())
    memory = r.memory_peak()

    everyone = done + list(live.values())
    tokens = sum(1 for d in everyone for t in d["stamps"]
                 if t_open <= t < t_close)
    gaps = [s[j] - s[j - 1] for d in everyone for s in [d["stamps"]]
            for j in range(1, len(s)) if t_open <= s[j] < t_close]
    e2e = {"tokens_per_s": tokens / r.seconds,
           "tpot_p95_ms": float(np.percentile(gaps, 95)) * 1e3
           if gaps else None}
    if backlog:
        attempted = sum(1 for d in everyone if d["stamps"]
                        and d["stamps"][0] < t_close
                        and d["stamps"][-1] >= t_open)
        failed = 0
        ttft = []
    else:
        by_uid = {d["req"].uid: d for d in everyone}
        window = [by_uid[q["uid"]] for q in due_in if q["uid"] in by_uid]
        ttft = [d["stamps"][0] - d["due"] for d in window if d["stamps"]]
        attempted = len(due_in)
        failed = attempted - len(ttft)
        e2e["ttft_p90_ms"] = float(np.percentile(ttft, 90)) * 1e3 \
            if ttft else None
    r.log(f"requests: {i} submitted, {len(done)} finished, {attempted} "
          f"attempted in the window, {failed} failed; {tokens} tokens and "
          f"{len(gaps)} gaps in the window; {len(ttft)} first tokens")
    r.log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
          f"max {max(late) * 1e3:.3f} ms over {len(late)} submissions")
    r.log(f"pending queue: {queue_open} at the window's opening, "
          f"{queue_close} at its close")
    r.log(f"ramp {ramp:g} s before the window; compiles inside the window: "
          f"{in_window[0]} lowered, {in_window[1]} compiled; device "
          f"peak_bytes_in_use {memory}")
    counters = {}
    if rec is not None:
        counters = {"model": m, "n_slots": ad.n_slots,
                    "block_size": ad.bs, "ticks": rec.ticks,
                    "prefills": rec.prefills, "tokens": trace_tokens}

    served = [(np.asarray(d["req"].prompt), list(d["req"].generated))
              for d in _sample(done, r.seed, int(mix["check_tokens"]),
                               int(mix["check_requests"]))]
    del gw, b, ad, params, rec, live, done, everyone, reqs
    gc.collect()
    t = time.perf_counter()
    res = r.reference().served_gaps(m, r.seed, served, control=r.control)
    limit = float(r.config["limits"]["max_logit_gap"])
    gaps = res["control_gaps"] if r.control else res["gaps"]
    widest = float(np.max(gaps)) if len(gaps) else float("inf")
    r.log(f"reference: {len(served)} requests, {len(gaps)} served "
          f"tokens compared in {time.perf_counter() - t:.1f} s; widest gap "
          f"{widest!r}, median {float(np.median(gaps)):.3g}"
          + (f" (control {r.control} in the program's place)"
             if r.control else ""))
    info = {"queue_open": queue_open, "queue_close": queue_close,
            "compiles_in_window": in_window[0],
            "served_tokens_compared": len(gaps)}
    if r.control:
        info["program"] = {"max_logit_gap": float(np.max(res["gaps"]))}
    return {"attempted": attempted, "failed": failed, "e2e": e2e,
            "counters": counters, "memory_peak_bytes": memory,
            "checks": [("max_logit_gap", widest, limit)], "info": info}
