"""Decode tick: host milliseconds per ``adapter.decode`` call (which ends
with the sampled tokens on the host), total over the traced ticks divided
by their number."""


def read(run):
    ticks = run["counters"].get("ticks")
    if not ticks:
        return None
    return 1e3 * sum(dt for dt, _ in ticks) / len(ticks)
