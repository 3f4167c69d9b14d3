"""Scheduler: mean share of the decode lanes that a traced tick ran, %."""


def read(run):
    c = run["counters"]
    ticks = c.get("ticks")
    if not ticks:
        return None
    return 100.0 * sum(len(lens) for _, lens in ticks) / (
        len(ticks) * c["n_slots"])
