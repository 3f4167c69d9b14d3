"""Whole step: model FLOP/s of the traced window over the chip's bf16
peak, %.  Counts every decoded token (matrix products, output head, and
attention over its live context) and every prompt token folded (matrix
products and attention; one output-head row per chunk)."""
from bench import costs


def read(run):
    c, tr, peak = run["counters"], run["trace"], run["peak"]
    if not c.get("ticks") or peak is None or tr.window_s <= 0:
        return None
    m, bs = c["model"], c["block_size"]
    flops = sum(costs.decode_flops(m, lens) for _, lens in c["ticks"])
    for _, n, skipped in c.get("prefills", ()):
        flops += costs.prefill_flops(m, skipped, n - skipped)
        flops += costs.prefill_head_flops(m, -(-(n - skipped) // bs))
    return 100.0 * flops / tr.window_s / peak["bf16_flops_per_s"]
