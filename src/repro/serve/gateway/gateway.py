"""Async micro-batching front door over virtual time.

JetStream/SHARK-style serving shape: a small fixed set of padded batch
shapes ("buckets"), one pre-compiled entry point per bucket, so the steady
state never recompiles regardless of how ragged the arrival process is.
The loop is a discrete-event simulation over virtual time — deterministic
given a trace, while service times can still be *measured* from the real
jitted computation (``service_model="measured"``) or pinned
(``service_model="fixed"``) for tests.

Per tick the gateway:
  1. admits arrivals into a bounded queue (admission control: beyond
     ``max_queue`` the request is rejected and counted — backpressure is
     explicit, not an OOM);
  2. flushes a batch when the largest bucket fills OR the oldest queued
     request hits its ``max_delay_s`` deadline, padding up to the smallest
     bucket that fits;
  3. runs the two pipeline stages (at-sensor stage feeds the link; the
     host stage occupies the server) and charges per-request telemetry.

The LM path (``PromptGateway``) fronts the family-generic slot batcher the
same way: arrivals admit into slots as they free up, one batched decode
tick per virtual-time step.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lenet
from repro.serve.gateway import frontend as fe
from repro.serve.gateway.sensors import Arrival
from repro.serve.gateway.slots import ContinuousBatcher, Request
from repro.serve.gateway.telemetry import RequestRecord, Telemetry


@dataclasses.dataclass(frozen=True)
class GatewayConfig:
    bucket_sizes: tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    max_queue: int = 128             # admission control bound
    max_delay_s: float = 0.02        # oldest-request flush deadline
    link_mbps: float = 32.0          # sensor->host link bandwidth (Mbit/s)
    service_model: str = "measured"  # "measured" | "fixed"
    fixed_service_s: float = 0.0     # per-batch service time for "fixed"

    def __post_init__(self):
        assert tuple(sorted(self.bucket_sizes)) == tuple(self.bucket_sizes)


class MicroBatchGateway:
    """The frame path: sensor fleet -> buckets -> frontend offload -> tail."""

    def __init__(self, cfg: GatewayConfig, spec: fe.FrontendSpec,
                 params=None, seed: int = 0):
        self.cfg = cfg
        self.spec = spec
        self.params = params if params is not None else \
            lenet.init(jax.random.key(seed), spec.lenet)
        # one fixed-shape entry point per bucket (never recompiles): a
        # function object of its own per bucket, since jit caches by
        # function, named so the trace's programs tell the stages apart
        self._sensor_fns, self._gateway_fns = {}, {}
        for bs in cfg.bucket_sizes:
            def sensor_stage(p, x):
                return fe.sensor_stage(p, x, spec)

            def gateway_stage(p, x):
                return fe.gateway_stage(p, x, spec)
            self._sensor_fns[bs] = jax.jit(sensor_stage)
            self._gateway_fns[bs] = jax.jit(gateway_stage)
        self._frame_energy_nj = fe.frame_energy_nj(spec)
        self._link_bytes = fe.link_bytes_per_frame(spec)
        self._sensor_lat = fe.sensor_latency_s(spec)
        self._link_lat = self._link_bytes * 8 / (cfg.link_mbps * 1e6)

    # -- compile management -------------------------------------------------
    def warmup(self) -> None:
        """Compile every bucket up front (steady state then never compiles)."""
        for bs in self.cfg.bucket_sizes:
            x = jnp.zeros((bs, self.spec.lenet.image_size,
                           self.spec.lenet.image_size,
                           self.spec.lenet.channels), jnp.uint8)
            payload = self._sensor_fns[bs](self.params, x)
            jax.block_until_ready(self._gateway_fns[bs](self.params, payload))

    def compile_counts(self) -> dict[int, int]:
        """jit-cache sizes per bucket (tests assert these stay at 1)."""
        return {bs: self._sensor_fns[bs]._cache_size()
                + self._gateway_fns[bs]._cache_size()
                for bs in self.cfg.bucket_sizes}

    def jit_fns(self) -> dict[str, object]:
        """Named jitted entry points, for obs.RecompileDetector.track."""
        fns: dict[str, object] = {}
        for bs in self.cfg.bucket_sizes:
            fns[f"sensor_b{bs}"] = self._sensor_fns[bs]
            fns[f"gateway_b{bs}"] = self._gateway_fns[bs]
        return fns

    def cost_args(self) -> dict[str, tuple]:
        """``jit_fns`` paired with representative abstract arguments, for
        obs.costmodel roofline attribution (``fn.lower(*args)`` — shapes
        only, nothing executes)."""
        out: dict[str, tuple] = {}
        ln = self.spec.lenet
        for bs in self.cfg.bucket_sizes:
            x = jax.ShapeDtypeStruct(
                (bs, ln.image_size, ln.image_size, ln.channels), jnp.uint8)
            out[f"sensor_b{bs}"] = (self._sensor_fns[bs], (self.params, x))
            payload = jax.eval_shape(self._sensor_fns[bs], self.params, x)
            out[f"gateway_b{bs}"] = (self._gateway_fns[bs],
                                     (self.params, payload))
        return out

    # -- one batch ----------------------------------------------------------
    def _bucket_for(self, n: int) -> int:
        for bs in self.cfg.bucket_sizes:
            if bs >= n:
                return bs
        return self.cfg.bucket_sizes[-1]

    def _serve_batch(self, frames: np.ndarray, bs: int):
        """Returns (predictions, host_service_seconds)."""
        x = jnp.asarray(frames)
        payload = jax.block_until_ready(
            self._sensor_fns[bs](self.params, x))   # at-sensor (not server time)
        t0 = time.perf_counter()
        logits = jax.block_until_ready(
            self._gateway_fns[bs](self.params, payload))
        svc = time.perf_counter() - t0
        if self.cfg.service_model == "fixed":
            svc = self.cfg.fixed_service_s
        return np.asarray(jnp.argmax(logits, -1)), svc

    # -- the event loop -----------------------------------------------------
    def run(self, arrivals: list[Arrival],
            telemetry: Telemetry | None = None, *,
            tracer=None, metrics=None, slo=None, flight=None,
            incident=None) -> Telemetry:
        # always-on flight mode: with a FlightRecorder but no tracer, spans
        # still flow — through a retention-free tracer whose only sink is
        # the bounded ring (nothing grows with run length)
        if flight is not None:
            from repro.serve.obs import Tracer
            if tracer is None:
                tracer = Tracer(retain=False, sink=flight)
            elif tracer.sink is None:
                tracer.sink = flight
            if metrics is not None and metrics.sink is None:
                metrics.sink = flight.observe_sample
        if incident is not None and incident.context_fn is None:
            incident.context_fn = self.debug_state
        tel = telemetry if telemetry is not None else Telemetry()
        arrivals = [a for a in arrivals if a.kind == "frame"]
        # payload hits the gateway queue after at-sensor compute + link time
        offset = self._sensor_lat + self._link_lat
        queue: deque[Arrival] = deque()
        max_bs = self.cfg.bucket_sizes[-1]
        now, i, n = 0.0, 0, len(arrivals)
        if metrics is not None:
            metrics.register("queue_depth", lambda: len(queue))
        # per-request energy attribution: the same addends, folded in the
        # same order, that land in each record's energy_nj — request spans
        # carry this dict so obs can check conservation bitwise
        parts = {"frontend_nj": self._frame_energy_nj,
                 "link_nj": fe.link_energy_nj(self._link_bytes)}
        energy_nj = 0.0
        for v in parts.values():
            energy_nj += v

        def admit_until(t: float):
            nonlocal i
            while i < n and arrivals[i].t + offset <= t:
                a = arrivals[i]
                i += 1
                rejected = len(queue) >= self.cfg.max_queue
                if rejected:
                    tel.drop(a.uid, "frame", "queue_full",
                             a.t + offset)    # backpressure: reject
                    if tracer is not None:
                        tracer.instant("drop", tid=a.uid, t=a.t + offset,
                                       args={"reason": "queue_full"})
                    if incident is not None:
                        incident.observe_drop(a.t + offset)
                else:
                    queue.append(a)
                if slo is not None:
                    # every admission decision is a drop_rate event; the
                    # burn engine sees rejections as budget burn
                    slo.observe_event("drop_rate", a.t + offset, rejected)

        while i < n or queue:
            if not queue:
                now = max(now, arrivals[i].t + offset)
            admit_until(now)
            if not queue:
                continue
            # wait (in virtual time) for a full bucket or the deadline
            deadline = queue[0].t + offset + self.cfg.max_delay_s
            while len(queue) < max_bs and i < n and \
                    arrivals[i].t + offset <= deadline:
                now = max(now, arrivals[i].t + offset)
                admit_until(now)
            if len(queue) < max_bs:
                now = max(now, deadline)
            batch = [queue.popleft()
                     for _ in range(min(len(queue), max_bs))]
            bs = self._bucket_for(len(batch))
            frames = np.zeros((bs,) + batch[0].payload.shape, np.uint8)
            for j, a in enumerate(batch):
                frames[j] = a.payload
            t_serve = now
            preds, svc = self._serve_batch(frames, bs)
            now += svc
            if tracer is not None:
                tracer.clock.advance(now)
                tracer.begin("batch", pid=1, tid=0, t=t_serve,
                             args={"bucket": bs, "n": len(batch)})
                tracer.end("batch", pid=1, tid=0, t=now)
            for j, a in enumerate(batch):
                if tracer is not None:
                    # the loop is virtual time, so the lifecycle is traced
                    # retroactively at completion with exact stamps
                    tracer.begin("request", tid=a.uid, t=a.t,
                                 args={"endpoint": a.endpoint})
                    tracer.begin("sensor_link", tid=a.uid, t=a.t)
                    tracer.end("sensor_link", tid=a.uid, t=a.t + offset)
                    tracer.begin("queue_wait", tid=a.uid, t=a.t + offset)
                    tracer.end("queue_wait", tid=a.uid, t=t_serve)
                    tracer.begin("serve", tid=a.uid, t=t_serve)
                    tracer.end("serve", tid=a.uid, t=now)
                    tracer.end("request", tid=a.uid, t=now,
                               args={"energy_parts": parts,
                                     "energy_nj": energy_nj})
                rec = RequestRecord(
                    uid=a.uid, endpoint=a.endpoint, kind="frame",
                    t_arrival=a.t, t_done=now, energy_nj=energy_nj,
                    link_bytes=self._link_bytes, output=int(preds[j]))
                tel.record(rec)
                if slo is not None:
                    slo.observe_record(rec)
            if slo is not None:
                slo.evaluate(now)
            if incident is not None:
                incident.poll(now)
            if metrics is not None:
                metrics.inc("frames_completed", len(batch))
                metrics.maybe_sample(now)
        if metrics is not None and metrics.samples:
            tel.record_series(metrics.samples)
        if incident is not None:
            incident.check_energy(tel, now)
        return tel

    def debug_state(self) -> dict:
        """Incident-bundle context: configuration + jit surface sizes (the
        frame path keeps no cross-run queue state)."""
        return {
            "kind": "frame_gateway",
            "config": dataclasses.asdict(self.cfg),
            "frontend": {"mode": self.spec.mode, "bits": self.spec.bits},
            "jit_cache_sizes": {name: fn._cache_size()
                                for name, fn in self.jit_fns().items()},
        }


def drive_prompt_loop(arrivals, tel: Telemetry, *, busy, queue_depth,
                      max_queue, submit, step, record,
                      clock=None, tracer=None, metrics=None,
                      slo=None, step_cost=None, incident=None) -> None:
    """The virtual-time event loop shared by the one-slice
    :class:`PromptGateway` and the sharded router (serve/shard/): drain
    arrivals into ``submit`` as virtual time reaches them (dropping, with
    accounting, beyond ``max_queue``), charge each ``step``'s measured
    wall time to the virtual clock, and ``record(req, now)`` every
    completion.  One driver means drop policy and clock accounting cannot
    drift between the two front doors.

    ``max_queue`` may be a callable returning the current bound — the
    SLO-driven backpressure path shrinks it under critical burn, so the
    gateway sheds early at admission instead of queueing work it already
    knows will miss its deadline.

    Observability (serve/obs/) rides on four optional hooks: ``clock``
    (a SimClock the loop advances, so the batcher can stamp dequeue/admit
    times), ``tracer`` (request/queue_wait spans open at submit; each
    ``step`` runs inside an ``anchor``/``release`` window so sub-tick
    spans interpolate between the tick's virtual endpoints), ``metrics``
    (interval snapshots after every tick), and ``slo`` (admission
    decisions feed the drop_rate objective; the burn engine evaluates
    once per tick, next to the metrics sampler).  All default to None,
    and the loop makes zero observability calls then.

    ``incident`` (an obs.IncidentCapture) observes every admission drop
    (the drop-burst trigger) and is polled once per tick for recompile
    leaks.  Its SLO ``warn -> critical`` trigger needs no loop hook: the
    pressure signal fires synchronously inside ``slo.evaluate`` below —
    *before* the next admission pass — so the bundle is on disk before the
    first pressure-shed drop is even decided.

    ``step_cost`` (optional, ``fn(wall_seconds) -> virtual_seconds``)
    re-prices a tick before it is charged to the clock.  The sharded
    router uses it to charge *concurrent-slice* time — slices are
    disjoint device groups that tick simultaneously in a real fleet, so
    a round costs the slowest slice's tick plus the router's serial
    overhead, not the sum a single-host simulation measures.  Mutually
    exclusive with ``tracer``: sub-tick spans interpolate real wall
    offsets inside each tick, which only stay inside the tick's virtual
    window under wall accounting (callers pass one or the other).
    """
    assert step_cost is None or tracer is None, \
        "step_cost re-pricing and wall-anchored tracing are exclusive"
    if tracer is not None and clock is None:
        clock = tracer.clock
    now, i, n = 0.0, 0, len(arrivals)
    while i < n or busy():
        if not busy():
            now = max(now, arrivals[i].t)
            if clock is not None:
                clock.advance(now)
        while i < n and arrivals[i].t <= now:
            a = arrivals[i]
            i += 1
            mq = max_queue() if callable(max_queue) else max_queue
            rejected = queue_depth() >= mq
            if slo is not None:
                slo.observe_event("drop_rate", now, rejected)
            if rejected:
                tel.drop(a.uid, "prompt", "queue_full", now)
                if tracer is not None:
                    tracer.instant("drop", tid=a.uid, t=now,
                                   args={"reason": "queue_full"})
                if incident is not None:
                    incident.observe_drop(now)
                continue
            if tracer is not None:
                # lifecycle span opens at *arrival* (the request waited
                # from a.t even if the loop reached it later)
                tracer.begin("request", tid=a.uid, t=a.t,
                             args={"endpoint": a.endpoint})
                tracer.begin("queue_wait", tid=a.uid, t=a.t)
            submit(a)
        if tracer is not None:
            tracer.anchor()
        t0 = time.perf_counter()
        finished = step()
        dt = time.perf_counter() - t0
        if step_cost is not None:
            dt = step_cost(dt)
        now += dt
        if clock is not None:
            clock.advance(now)
        if tracer is not None:
            tracer.release()
        for req in finished:
            record(req, now)
        # evaluate before sampling so the burn/state gauges the evaluation
        # pushes land in this tick's snapshot, not the next one
        if slo is not None:
            slo.evaluate(now)
        if incident is not None:
            incident.poll(now)
        if metrics is not None:
            metrics.maybe_sample(now)


def record_prompt_completion(tel: Telemetry, req, now: float,
                             t_arrival: float, endpoint: int,
                             token_energy_nj: float, bytes_per_token: int,
                             energy_spec: "fe.FrontendSpec | None" = None,
                             tracer=None, slo=None) -> None:
    """Charge one finished LM request into the ledger — the single pricing
    path shared by :class:`PromptGateway` and the sharded router
    (serve/shard/router.py), so the energy model cannot drift between the
    one-slice and multi-slice front doors.

    Prefix-cache resumes skip the frontend compute for the shared prompt
    tokens (the link still carries every token); cross-slice migration
    bytes, when present on the request, are priced through
    :func:`frontend.migration_energy_nj`.

    The stage-attributed parts (frontend prefill / frontend decode / link /
    migration — each an independent product, so the split itself introduces
    no rounding) are folded left-to-right into ``energy_nj`` and — when a
    ``tracer`` is attached — stamped onto the closing request span, so the
    span stream's energy sum reproduces the ledger total bitwise
    (``obs.Tracer.assert_energy_conserved``) and obs.costmodel can join
    per-stage nJ against the roofline stages.  An attached ``slo`` monitor
    observes the completion (TTFT / TPOT / queue-wait) as it is recorded.
    """
    n_tokens = len(req.prompt) + len(req.generated)
    processed = n_tokens - req.prefill_tokens_skipped
    link = bytes_per_token * n_tokens
    # tokens the batched decode tick produced vs tokens the prefill pass
    # processed (the first generated token comes out of prefill)
    decode_tok = max(0, len(req.generated) - 1)
    parts = {"frontend_prefill_nj": token_energy_nj
             * (processed - decode_tok),
             "frontend_decode_nj": token_energy_nj * decode_tok,
             "link_nj": fe.link_energy_nj(link)}
    migration_bytes = getattr(req, "migration_bytes", 0)
    if migration_bytes and energy_spec is not None:
        parts["migration_nj"] = fe.migration_energy_nj(energy_spec,
                                                       migration_bytes)
    energy_nj = 0.0
    for v in parts.values():
        energy_nj += v
    rec = RequestRecord(
        uid=req.uid, endpoint=endpoint, kind="prompt",
        t_arrival=t_arrival, t_done=now, energy_nj=energy_nj,
        link_bytes=link, output=req.generated[-1],
        kv_blocks=req.kv_blocks,
        prefix_hit_blocks=req.prefix_hit_blocks,
        prefill_tokens_skipped=req.prefill_tokens_skipped,
        energy_saved_nj=token_energy_nj * req.prefill_tokens_skipped,
        migration_bytes=migration_bytes,
        migrations=getattr(req, "migrations", 0),
        t_dequeue=getattr(req, "t_dequeue", -1.0),
        t_admit=getattr(req, "t_admit", -1.0),
        tokens_out=len(req.generated))
    tel.record(rec)
    if slo is not None:
        slo.observe_record(rec)
    if tracer is not None:
        if tracer.innermost(tid=req.uid) != "request":
            # the request's whole active life predates the tracer wiring
            # (direct submit + step before run): open its span late, at
            # arrival, so every completed uid still carries a request span
            # with conserved energy parts
            tracer.begin("request", tid=req.uid, t=t_arrival,
                         args={"late_open": True})
        tracer.end("request", tid=req.uid, t=now,
                   args={"energy_parts": parts, "energy_nj": energy_nj,
                         "tokens_out": len(req.generated)})


class PromptGateway:
    """The LM path: arrivals -> family-generic slot batcher, virtual time.

    Same contracts as the frame path: ``warmup`` pre-compiles prefill (per
    prompt length) and the batched decode so one-time XLA compilation never
    lands in the virtual clock, and admission is bounded by ``max_queue``
    (excess prompts are rejected and counted, not queued without bound).

    LM requests are charged energy the same way frames are: per processed
    token, the calibrated Table-3 model projected onto the embedding-row
    geometry (``frontend.lm_token_energy_nj``), plus link energy on the
    token bytes — so every request in the ledger, frame or prompt, carries
    a J/inference figure.  When the batcher runs over the paged KV adapter,
    the pool's counters are snapshotted into the telemetry at drain.
    """

    def __init__(self, batcher: ContinuousBatcher, max_new_tokens: int = 16,
                 bytes_per_token: int = 4, max_queue: int = 64,
                 energy_spec: fe.FrontendSpec | None = None,
                 tracer=None, metrics=None, slo=None,
                 shed_factor: int = 4, flight=None, incident=None):
        self.batcher = batcher
        self.max_new_tokens = max_new_tokens
        self.bytes_per_token = bytes_per_token
        self.max_queue = max_queue
        if energy_spec is None:
            energy_spec = fe.FrontendSpec()
        self.energy_spec = energy_spec
        self._token_energy_nj = fe.lm_token_energy_nj(
            energy_spec, batcher.adapter.cfg.d_model)
        # observability (serve/obs/): all default None and are wired into
        # the batcher only for the duration of run() — warmup stays
        # untraced and a gateway without a tracer makes zero obs calls
        self.tracer = tracer
        self.metrics = metrics
        self.slo = slo
        # flight recorder + incident forensics (serve/obs/flight.py,
        # incident.py): with a FlightRecorder but no tracer, run() creates
        # a retention-free tracer whose only sink is the bounded ring —
        # always-on span capture without an unbounded event list; an
        # IncidentCapture snapshots the ring (plus debug_state) on its
        # triggers, and capture_incident() does so on demand
        self.flight = flight
        self.incident = incident
        if incident is not None and incident.context_fn is None:
            incident.context_fn = self.debug_state
        # SLO-driven backpressure: subscribe to the monitor's pressure
        # signal; under critical burn the admission bound shrinks by
        # shed_factor, so overload sheds at the door (cheap, counted)
        # instead of queueing work that will blow its deadline anyway.
        # The same hook is where the planned closed-loop bit-width
        # degradation controller will step endpoints down the stream-length
        # ladder (ROADMAP).
        self.shed_factor = shed_factor
        self._shedding = False
        if slo is not None:
            slo.pressure.subscribe(self._on_pressure)

    def _on_pressure(self, event) -> None:
        self._shedding = event.state == "critical"

    def _admit_bound(self) -> int:
        if self._shedding:
            return max(1, self.max_queue // self.shed_factor)
        return self.max_queue

    def jit_fns(self) -> dict[str, object]:
        """Named jitted entry points, for obs.RecompileDetector.track."""
        fns = getattr(self.batcher.adapter, "jit_fns", None)
        return fns() if fns is not None else {}

    def cost_args(self) -> dict[str, tuple]:
        """Adapter stages + representative args, for obs.costmodel
        roofline attribution (see the adapters' ``cost_args``)."""
        fns = getattr(self.batcher.adapter, "cost_args", None)
        return fns() if fns is not None else {}

    def warmup(self, prompt_lens: tuple[int, ...], vocab: int = 2) -> None:
        """Drain one dummy request per prompt length through the batcher
        (compiles prefill for each length + the batched decode); adapters
        clear slot state on retire, so real traffic is unaffected.
        max_new_tokens=2 forces at least one decode tick — a 1-token budget
        would retire at admission and leave decode uncompiled."""
        for j, n in enumerate(prompt_lens):
            self.batcher.submit(Request(
                uid=-1 - j, prompt=np.zeros((n,), np.int32),
                max_new_tokens=2))
        self.batcher.run()

    def run(self, arrivals: list[Arrival],
            telemetry: Telemetry | None = None) -> Telemetry:
        tel = telemetry if telemetry is not None else Telemetry()
        arrivals = [a for a in arrivals if a.kind == "prompt"]
        arr_t = {a.uid: a.t for a in arrivals}
        arr_ep = {a.uid: a.endpoint for a in arrivals}
        pool_stats = getattr(self.batcher.adapter, "pool_stats", None)
        if self.flight is not None:
            from repro.serve.obs import Tracer
            if self.tracer is None:
                # always-on mode: the bounded ring is the only retention
                self.tracer = Tracer(retain=False, sink=self.flight)
            elif self.tracer.sink is None:
                self.tracer.sink = self.flight
            if self.metrics is not None and self.metrics.sink is None:
                self.metrics.sink = self.flight.observe_sample
        # SLO timestamps (t_dequeue/t_admit) need a shared virtual clock
        # even when no tracer is attached
        from repro.serve.obs import SimClock
        clock = self.tracer.clock if self.tracer is not None else SimClock()
        if self.metrics is not None:
            m = self.metrics
            m.register("queue_depth", lambda: len(self.batcher.pending))
            m.register("active_slots", lambda: self.batcher.last_active)
            pool = getattr(self.batcher.adapter, "pool", None)
            if pool is not None:
                for name in pool.gauges():
                    m.register(name, lambda n=name: pool.gauges()[n])
            cascade = getattr(self.batcher.adapter, "cascade_stats", None)
            if cascade is not None and \
                    getattr(self.batcher.adapter, "backend", None) \
                    == "cascade":
                # cascade_* gauges -> repro_cascade_* OpenMetrics families
                for key in ("groups", "grouped_lanes", "prefix_rows",
                            "prefix_rows_flat"):
                    m.register(f"cascade_{key}",
                               lambda k=key: cascade()[k])
        self.batcher.clock = clock
        self.batcher.tracer = self.tracer
        self.batcher.adapter.tracer = self.tracer
        try:
            drive_prompt_loop(
                arrivals, tel,
                busy=lambda: self.batcher.busy,
                queue_depth=lambda: len(self.batcher.pending),
                max_queue=self._admit_bound,
                submit=lambda a: self.batcher.submit(Request(
                    uid=a.uid, prompt=np.asarray(a.payload, np.int32),
                    max_new_tokens=self.max_new_tokens)),
                step=self.batcher.step,
                record=lambda req, now: record_prompt_completion(
                    tel, req, now, arr_t[req.uid], arr_ep[req.uid],
                    self._token_energy_nj, self.bytes_per_token,
                    self.energy_spec, tracer=self.tracer, slo=self.slo),
                clock=clock, tracer=self.tracer, metrics=self.metrics,
                slo=self.slo, incident=self.incident)
        finally:
            self.batcher.clock = None
            self.batcher.tracer = None
            self.batcher.adapter.tracer = None
        if pool_stats is not None:
            tel.record_pool(pool_stats())
        if self.metrics is not None and self.metrics.samples:
            tel.record_series(self.metrics.samples)
        if self.incident is not None:
            self.incident.check_energy(tel, clock.t)
        return tel

    def debug_state(self) -> dict:
        """Forensic gateway state for incident bundles: batcher occupancy,
        pool + radix debug snapshot, cascade grouping, jit-cache sizes —
        aggregate state only, no request payloads."""
        ad = self.batcher.adapter
        state: dict = {
            "kind": "prompt_gateway",
            "max_new_tokens": self.max_new_tokens,
            "max_queue": self.max_queue,
            "admit_bound": self._admit_bound(),
            "shedding": self._shedding,
            "batcher": self.batcher.debug_state(),
            "jit_cache_sizes": {name: fn._cache_size()
                                for name, fn in self.jit_fns().items()},
        }
        pool = getattr(ad, "pool", None)
        if pool is not None:
            state["pool"] = pool.debug_snapshot()
        if getattr(ad, "backend", None) is not None:
            state["backend"] = ad.backend
        if getattr(ad, "backend", None) == "cascade":
            state["cascade"] = ad.cascade_stats()
        return state

    def capture_incident(self, reason: str, *, extra: dict | None = None):
        """Explicit forensic capture: snapshot the flight ring + debug
        state into a bundle right now (trigger ``explicit``).  Requires an
        IncidentCapture attached at construction."""
        if self.incident is None:
            raise RuntimeError(
                "capture_incident() needs an IncidentCapture attached "
                "(PromptGateway(..., incident=...) or "
                "ServeSpec(incident_dir=...))")
        return self.incident.capture(reason, extra=extra)
