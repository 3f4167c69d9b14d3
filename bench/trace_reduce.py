"""From a profiler trace to busy time, kernel time and labelled idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
two lists: the device's operations (the ``XLA Ops`` line of each TPU
plane) and the benchmark's own host spans (``TraceAnnotation`` events
named ``bench:...``).  ``Trace`` then answers, inside the traced window
(the host span ``bench:window``):

- ``busy_s``: length of the union of the intervals in which an operation
  ran on the device, averaged over the devices that ran any;
- ``op_seconds``: device time per operation, by the HLO instruction's name
  (``%copy.10``; the trace names an event by its whole instruction text).
  The ``XLA Ops`` line nests a loop's body inside the loop's own event, so
  sums count only the innermost events;
- ``seconds_matching`` / ``seconds_within``: device time of the
  operations whose name matches, or that ran inside host spans of a name;
- ``idle_gaps``: the device's idle time, each gap charged to the innermost
  benchmark span open on the host at its midpoint ("host:none" if none).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench:window"
OPS_LINE = "XLA Ops"


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> "Trace":
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    host: list[tuple[str, float, float]] = []
    device: dict[str, list[tuple[str, float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs = [(e.name.split(" = ", 1)[0], e.start_ns,
                            e.start_ns + e.duration_ns) for e in line.events]
                    if evs:
                        device.setdefault(plane.name, []).extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith("bench:"))
    return Trace(host, device)


def _leaves(evs):
    """The events that hold no other event of the line."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    parent = [False] * len(evs)
    stack: list[int] = []
    for i in order:
        while stack and evs[stack[-1]][2] <= evs[i][1]:
            stack.pop()
        if stack and evs[i][2] <= evs[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [e for e, p in zip(evs, parent) if not p]


def _union(intervals):
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class Trace:
    """Device operations and host spans of one traced window (ns)."""

    def __init__(self, host, device):
        windows = [s for s in host if s[0] == WINDOW]
        if not windows:
            raise ValueError(f"the trace holds no {WINDOW!r} span")
        _, self.t0, self.t1 = windows[0]
        self.spans = sorted((s for s in host if s[0] != WINDOW),
                            key=lambda s: s[1])
        self.device = {
            dev: [(n, max(a, self.t0), min(b, self.t1)) for n, a, b in evs
                  if b > self.t0 and a < self.t1]
            for dev, evs in device.items()}
        self.device = {d: e for d, e in self.device.items() if e}
        self.leaves = {d: _leaves(e) for d, e in self.device.items()}

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.device:
            return 0.0
        per = [sum(b - a for a, b in _union((a, b) for _, a, b in evs))
               for evs in self.device.values()]
        return sum(per) / len(per) * 1e-9

    def _ops(self):
        for evs in self.leaves.values():
            yield from evs

    def op_seconds(self) -> dict[str, float]:
        out: dict[str, float] = defaultdict(float)
        for n, a, b in self._ops():
            out[n] += (b - a) * 1e-9
        return dict(out)

    def seconds_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(b - a for n, a, b in self._ops() if rx.search(n)) * 1e-9

    def seconds_within(self, span: str) -> float:
        """Device time that overlaps host spans named ``span``."""
        spans = _union((a, b) for n, a, b in self.spans if n == span)
        starts = [a for a, _ in spans]
        total = 0.0
        for _, a, b in self._ops():
            i = bisect.bisect_right(starts, b) - 1
            while i >= 0 and spans[i][1] > a:
                total += max(0.0, min(b, spans[i][1]) - max(a, spans[i][0]))
                i -= 1
        return total * 1e-9

    def count(self, span: str) -> int:
        return sum(1 for n, _, _ in self.spans if n == span)

    def _label(self, t: float) -> str:
        """Innermost benchmark span open on the host at ``t``."""
        best = None
        for n, a, b in self.spans:
            if a > t:
                break
            if b >= t and (best is None or a >= best[1]):
                best = (n, a)
        return best[0] if best else "host:none"

    def idle_gaps(self) -> dict[str, float]:
        """Idle seconds of the device charged to what the host was doing
        (the first device only: one chip per cell)."""
        if not self.device:
            return {"host:none": self.window_s}
        evs = next(iter(self.device.values()))
        busy = _union((a, b) for _, a, b in evs)
        out: dict[str, float] = defaultdict(float)
        t = self.t0
        for a, b in busy + [[self.t1, self.t1]]:
            if a > t:
                out[self._label((t + a) / 2)] += (a - t) * 1e-9
            t = max(t, b)
        return dict(out)

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k, v] for k, v in
                    sorted(d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": best(self.op_seconds()),
                "idle_gaps": best(self.idle_gaps())}
