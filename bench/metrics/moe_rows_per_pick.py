"""MoE decode: rows the expert FFN computed in the traced ticks over the
router picks the active lanes needed (lanes x top_k x MoE layers).  One
where only the picked rows are computed; the number of experts times the
one-token dispatch group's capacity over ``top_k`` where every lane fills
every expert's buffer."""


def read(run):
    c = run["counters"]
    rows, picks = c.get("moe_rows"), c.get("moe_picks")
    if rows is None or not picks:
        return None
    return rows / picks
