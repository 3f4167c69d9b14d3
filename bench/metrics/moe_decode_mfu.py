"""Whole step of the latent-attention MoE cells: model FLOP/s of the
traced window over the chip's bf16 peak, %.  Counts what the mathematics
needs (``bench/costs_mla.py``): every decoded token's active parameters
and its absorbed latent attention over the live context, and every prompt
token folded (one output-head row per chunk)."""
from bench import costs_mla


def read(run):
    c, tr, peak = run["counters"], run["trace"], run["peak"]
    m = c.get("model") or {}
    if not c.get("ticks") or not m.get("kv_lora_rank") or peak is None \
            or tr.window_s <= 0:
        return None
    bs = c["block_size"]
    flops = sum(costs_mla.decode_flops(m, lens) for _, lens in c["ticks"])
    for _, n, skipped in c.get("prefills", ()):
        flops += costs_mla.prefill_flops(m, skipped, n - skipped)
        flops += costs_mla.prefill_head_flops(m, -(-(n - skipped) // bs))
    return 100.0 * flops / tr.window_s / peak["bf16_flops_per_s"]
