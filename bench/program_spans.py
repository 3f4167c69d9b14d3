"""The serving program's own spans in a profiler trace, beside the device.

With a ``Tracer(clock=WallClock())`` attached to the batcher and its
adapter, every scoped span of the program (``Tracer.span``) is also a
``TraceAnnotation`` named ``repro:<name>`` on the profiler's host plane,
on the same clock as the device's ``XLA Ops`` line.  ``load`` keeps those
events, with their args, beside the ``bench/trace_reduce.py`` reduction
of the same file (which it leaves as it is), and ``Program`` answers,
inside the traced window (``bench:window``):

- ``program_count`` / ``program_seconds``: events of a name, and their
  summed host time;
- ``idle_inside`` / ``idle_outside``: the first device's idle seconds
  inside, or outside, the union of the events of a name.  The two add up
  to the window's idle time.

``tick_split`` turns these into per-tick milliseconds of the decode
tick's host phases, divided by the number of ``repro:tick.dispatch``
events: the split of the host round trip that a tick costs.
"""
from __future__ import annotations

from bench import trace_reduce

PREFIX = "repro:"
TICK = PREFIX + "tick"
# the tick's children, in the order they run; they do not overlap
TICK_PARTS = ("admit", "tick.prepare", "tick.dispatch", "tick.sample",
              "retire")


def load(path: str) -> "Program":
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                              dict(e.stats)) for e in line.events
                             if e.name.startswith(PREFIX))
    return Program(trace_reduce.load(path), spans)


def _overlap(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


class Program:
    """The ``repro:`` host events of one traced window (ns), clipped to the
    window, beside the window's ``trace_reduce.Trace``."""

    def __init__(self, trace: trace_reduce.Trace, spans):
        self.trace = trace
        self.spans = sorted(
            ((n, max(a, trace.t0), min(b, trace.t1), args)
             for n, a, b, args in spans if b > trace.t0 and a < trace.t1),
            key=lambda s: s[1])

    def program_count(self, name: str) -> int:
        return sum(1 for n, _, _, _ in self.spans if n == name)

    def program_seconds(self, name: str) -> float:
        return sum(b - a for n, a, b, _ in self.spans if n == name) * 1e-9

    def args(self, name: str) -> list[dict]:
        return [args for n, _, _, args in self.spans if n == name]

    def _idle(self) -> list[tuple[float, float]]:
        """Idle intervals of the first device in the window, as
        ``Trace.idle_gaps`` finds them (all of it with no device)."""
        tr = self.trace
        if not tr.device:
            return [(tr.t0, tr.t1)]
        evs = next(iter(tr.device.values()))
        out, t = [], tr.t0
        for a, b in trace_reduce._union((a, b) for _, a, b in evs) \
                + [[tr.t1, tr.t1]]:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        return out

    def idle_inside(self, name: str) -> float:
        spans = trace_reduce._union((a, b) for n, a, b, _ in self.spans
                                    if n == name)
        return _overlap(self._idle(), spans) * 1e-9

    def idle_outside(self, name: str) -> float:
        idle = sum(b - a for a, b in self._idle()) * 1e-9
        return idle - self.idle_inside(name)


def tick_split(prog: Program) -> dict:
    """Per decode tick, in ms: host time of ``tick.prepare`` and
    ``tick.dispatch``; the device's idle time inside ``repro:tick``
    (``tick_idle_ms``) and outside it, in the load generator's own loop
    (``loop_idle_ms``); and that idle time inside each of the tick's
    children, with ``self`` for the part no child covers.  The idle
    numbers are left out where the trace has no device.  Empty where no
    tick dispatched."""
    ticks = prog.program_count(PREFIX + "tick.dispatch")
    if not ticks:
        return {}
    per = 1e3 / ticks
    out = {"ticks": ticks,
           "tick_prepare_ms.decode":
           per * prog.program_seconds(PREFIX + "tick.prepare"),
           "tick_dispatch_ms.decode":
           per * prog.program_seconds(PREFIX + "tick.dispatch"),
           "tick_sample_ms": per * prog.program_seconds(PREFIX + "tick.sample"),
           "tick_host_ms": per * prog.program_seconds(TICK)}
    prepare = prog.args(PREFIX + "tick.prepare")
    out["lanes_per_tick"] = sum(a.get("active", 0) for a in prepare) / ticks
    out["live_tokens_per_tick"] = sum(a.get("live_tokens", 0)
                                      for a in prepare) / ticks
    admits = prog.args(PREFIX + "admit")
    out["admitted"] = sum(a.get("admitted", 0) for a in admits)
    out["prompt_tokens"] = sum(a.get("prompt_tokens", 0) for a in admits)
    if prog.trace.device:
        inside = prog.idle_inside(TICK)
        out["tick_idle_ms.decode"] = per * inside
        out["loop_idle_ms.decode"] = per * prog.idle_outside(TICK)
        parts = {p: per * prog.idle_inside(PREFIX + p) for p in TICK_PARTS}
        parts["self"] = per * inside - sum(parts.values())
        out["tick_idle_ms_by_part"] = parts
    return out
