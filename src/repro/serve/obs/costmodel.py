"""Per-stage cost/roofline attribution from XLA cost analysis + spans.

Joins two sources the stack already exposes:

  static   every serving component's ``cost_args()`` registry — the same
           jitted entry points ``jit_fns()`` tracks for recompiles, paired
           with representative steady-state-shaped arguments — lowered
           through ``fn.lower(*args).compile().cost_analysis()`` for the
           executable's FLOPs and bytes accessed;
  dynamic  the tracer's measured span durations for the stage's serving
           span (decode ``tick``, ``prefill_chunk`` folds, frame ``batch``
           steps, ``migrate`` copies).

Per stage the attributor reports arithmetic intensity (FLOPs/byte),
achieved FLOP/s and B/s over the measured spans, and a roofline verdict:
**compute-bound** when intensity clears the ridge point, **memory-bound**
below it.  The default ridge (:data:`DEFAULT_RIDGE`) sits between the two
regimes this stack actually exhibits — the in-place paged decode tick
streams the whole live KV arena for a (1-token × batch) matmul and lands
well under it; the chunked-prefill fold amortizes the weight traffic over
a full block of tokens and lands well over it.  That verdict is exactly
the classification the disaggregated prefill/decode split wants, and the
known hard axis for SC datapaths, where stream length multiplies both
terms at once.

Cost analysis is best-effort by contract: under ``REPRO_KERNELS_INTERPRET``
or non-XLA backends, ``cost_analysis()`` may be empty, partial, or raise.
:func:`analyze` returns what it can and the attributor degrades per stage —
``source`` is ``"xla"`` (both terms), ``"bytes-only"`` (no FLOP count;
verdict from traffic alone), or ``"measured-only"`` (no analysis at all;
span timings still attributed, verdict ``"unknown"``) — never an obs-path
crash.

The energy cross-check (:func:`stage_energy`) re-folds the request spans'
``energy_parts`` into per-stage nJ totals; the grand total reproduces the
telemetry ledger's conserved ``fleet_energy_nj`` bitwise, because the span
stream carries the ledger's own addends in fold order.
"""
from __future__ import annotations

import math

from repro.serve.obs.tracer import _bump

# roofline ridge point (FLOPs/byte) separating this stack's two regimes:
# bench-config in-place decode ticks measure ~0.36 F/B, chunked prefill
# folds ~1.0+ F/B, so 0.6 classifies both with ~1.7x margin
DEFAULT_RIDGE = 0.6

# stage base name -> the traced serving span whose measured durations the
# stage's cost attributes over (stages without one are static-only)
STAGE_SPANS = (
    ("decode", "tick"),
    ("chunk_fold", "prefill_chunk"),
    ("prefill", "prefill"),
    ("copy", "migrate"),
    ("sensor", "batch"),
    ("gateway", "batch"),
)


def span_for(stage: str) -> str | None:
    """Serving span name for a ``cost_args()`` stage key (slice prefixes
    ``sliceN.`` and bucket suffixes ``_b8`` stripped)."""
    base = stage.rsplit(".", 1)[-1]
    for key, span in STAGE_SPANS:
        if base == key or base.startswith(key + "_"):
            return span
    return None


def analyze(fn, args) -> dict | None:
    """FLOPs + bytes accessed for one jitted entry point via AOT lowering,
    or None when the backend offers no analysis (interpret mode, non-XLA
    paths) — callers degrade, they never crash.

    ``cost_analysis()`` returns a dict, or None / an empty or key-less
    dict where the backend has nothing to say.
    """
    try:
        ca = fn.lower(*args).compile().cost_analysis()
    except Exception:
        return None
    if not isinstance(ca, dict):
        return None
    flops = float(ca.get("flops", 0.0) or 0.0)
    nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    return {"flops": flops, "bytes": nbytes}


def attribute(stages: dict, tracer=None, *, ridge: float = DEFAULT_RIDGE,
              telemetry=None) -> dict:
    """Roofline-attribute every stage of a ``cost_args()`` registry.

    ``stages`` maps stage name -> ``(jitted_fn, args)``.  Returns
    ``{"stages": {name: entry}, "ridge_flops_per_byte": ...,
    "energy": ...}`` where each entry carries the static cost (per call),
    the measured span aggregate (count, seconds), the achieved rates, and
    the verdict + its provenance (``source``).  With a ``telemetry``
    ledger attached, the per-stage energy re-fold rides along.
    """
    _bump()
    out: dict = {"ridge_flops_per_byte": ridge, "stages": {}}
    for name, (fn, args) in stages.items():
        cost = analyze(fn, args)
        span = span_for(name)
        spans = tracer.spans(span) if tracer is not None and span else []
        calls = len(spans)
        measured_s = math.fsum(s["dur"] for s in spans)
        entry = {"span": span, "calls": calls, "measured_s": measured_s}
        if cost is None:
            entry.update(source="measured-only", flops=None, bytes=None,
                         intensity=None, verdict="unknown")
        else:
            flops, nbytes = cost["flops"], cost["bytes"]
            if flops > 0.0 and nbytes > 0.0:
                intensity = flops / nbytes
                entry.update(source="xla", intensity=intensity,
                             verdict="compute-bound" if intensity >= ridge
                             else "memory-bound")
            else:
                # a byte count with no FLOP count still classifies: pure
                # traffic sits at intensity 0, under any ridge
                entry.update(source="bytes-only", intensity=0.0,
                             verdict="memory-bound")
            entry.update(flops=flops, bytes=nbytes)
            if measured_s > 0.0:
                entry["achieved_flops_per_s"] = flops * calls / measured_s
                entry["achieved_bytes_per_s"] = nbytes * calls / measured_s
        out["stages"][name] = entry
    if telemetry is not None and tracer is not None:
        out["energy"] = stage_energy(tracer, telemetry)
    return out


def stage_energy(tracer, telemetry=None) -> dict:
    """Per-stage nJ re-fold of the span stream's ``energy_parts``.

    Stage totals (``fsum`` per part key) answer "where did the energy
    go"; ``total_nj`` left-folds each request's parts in ledger order, so
    when a ``telemetry`` ledger is passed, ``conserved`` asserts the
    cross-check **bitwise** against ``fleet_energy_nj`` — per-stage
    attribution that doesn't re-fold to the conserved ledger means a path
    charged energy the ledger never saw.
    """
    _bump()
    parts_all: dict[str, list[float]] = {}
    total = 0.0
    n = 0
    for e in tracer.events:             # append order == ledger record order
        if e["ph"] != "X" or e["name"] != "request":
            continue
        parts = e["args"].get("energy_parts") or {}
        span_e = 0.0
        for k, v in parts.items():      # ledger fold order per request
            parts_all.setdefault(k, []).append(v)
            span_e += v
        total += span_e
        n += 1
    out = {"stages_nj": {k: math.fsum(v) for k, v in parts_all.items()},
           "total_nj": total, "n_requests": n}
    if telemetry is not None:
        out["fleet_energy_nj"] = telemetry.fleet_energy_nj
        out["conserved"] = total == telemetry.fleet_energy_nj
    return out
