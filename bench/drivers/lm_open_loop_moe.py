"""Front door of the MoE LM cells: ``lm_open_loop``'s loop, unchanged, over
the paged gateway, given this configuration's weights
(``bench/weights_mla_moe.py``), the MoE work counters and the comparison
that suits a router.

``lm_open_loop`` is loaded as the harness loads a driver, a module of its
own, so what is set on it here touches no other cell.  Its weights call
makes the latent-attention MoE pytree, and its recorder of the traced
window hands over the adapter, whose running totals of expert-FFN rows and
router picks (``moe_rows_total``, ``moe_picks_total``) are read just
before ``r.trace_begin()`` and just after ``r.trace_end()``.  Their
differences go to ``counters`` as ``moe_rows`` and ``moe_picks``; an
adapter without the totals leaves them out.

``correct``: every served token's gap is read as the loop reads it (how
far the reference's logit of the token lies below the reference's best),
and a token is wide when its gap exceeds the configuration's
``max_logit_gap``.  The check is the share of wide tokens among those
compared, held to ``share_over_max_logit_gap``.  The widest gap alone
cannot be the check here: where the bfloat16 program rounds a router
score across the margin between two experts, that token takes another
expert and its logits move by whole units, in the program and in the
float8 control alike, so the widest gap of a few thousand tokens reads
the same on both sides.  It is kept in ``info`` as ``widest_gap``.  With
``r.control`` the control's tokens are counted, and the program's own
share of the same run goes to ``info["program"]``.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from bench import harness, weights_mla_moe

CHECK = "share_over_max_logit_gap"


def _totals(adapter):
    rows = getattr(adapter, "moe_rows_total", None)
    picks = getattr(adapter, "moe_picks_total", None)
    return None if rows is None or picks is None else (rows, picks)


def wide_share(gaps, limit: float) -> float:
    """Share of ``gaps`` above ``limit``; 1.0 where nothing was compared,
    so that a run with no answers cannot pass."""
    gaps = np.asarray(gaps)
    return float(np.mean(gaps > limit)) if gaps.size else 1.0


def run(r) -> dict:
    loop = harness.module("drivers", "lm_open_loop")
    loop.weights = SimpleNamespace(decoder_params=weights_mla_moe.params)
    held: dict = {}

    class Recorder(loop._Recorder):
        def __init__(self, adapter):
            super().__init__(adapter)
            held["adapter"] = adapter

    loop._Recorder = Recorder
    begin, end, reference = r.trace_begin, r.trace_end, r.reference

    def trace_begin():
        held["begin"] = _totals(held.get("adapter"))
        begin()

    def trace_end():
        end()
        # the adapter is dropped here, so that the reference pass after
        # the window finds the program's memory freed
        now = _totals(held.pop("adapter", None))
        if now is not None and held.get("begin") is not None:
            held["window"] = tuple(a - b for a, b in zip(now, held["begin"]))

    def served_gaps(*args, **kwargs):
        held["gaps"] = reference().served_gaps(*args, **kwargs)
        return held["gaps"]

    r.trace_begin, r.trace_end = trace_begin, trace_end
    r.reference = lambda: SimpleNamespace(served_gaps=served_gaps)
    out = loop.run(r)
    if "window" in held:
        out["counters"]["moe_rows"], out["counters"]["moe_picks"] = \
            held["window"]

    limits, res = r.config["limits"], held["gaps"]
    wide = float(limits["max_logit_gap"])
    gaps = res["control_gaps"] if r.control else res["gaps"]
    share = wide_share(gaps, wide)
    info = out["info"]
    info["widest_gap"] = float(np.max(gaps)) if len(gaps) else None
    if r.control:
        info["program"] = {CHECK: wide_share(res["gaps"], wide)}
    r.log(f"{CHECK}: {share!r} of {len(gaps)} served tokens have a gap "
          f"above {wide!r}")
    out["checks"] = [(CHECK, share, float(limits[CHECK]))]
    return out
