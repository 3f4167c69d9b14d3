"""Kernels: the latent (MLA) decode read's share of its roofline, %.

Least time of each traced tick: the larger of the bytes the read needs
(one latent row per live position per layer, the query and the output;
``bench/costs_mla.py``) over the peak bandwidth and its operations over
the peak bf16 rate; over the summed device time of the kernel's events.
The kernel is the Mosaic custom call of ``latent_decode_attention`` in
``kernels/paged_attn.py`` (its ``pallas_call`` ``name=``), once per layer
and tick.
"""
from bench import costs_mla

KERNEL = r"^%latent_decode_attention"


def read(run):
    ticks, tr, peak = run["counters"].get("ticks"), run["trace"], run["peak"]
    if not ticks or peak is None:
        return None
    spent = tr.seconds_matching(KERNEL)
    if spent <= 0:
        return None
    least = 0.0
    for _, lens in ticks:
        flops, nbytes = costs_mla.latent_attn_cost(run["counters"]["model"],
                                                   lens)
        least += max(nbytes / peak["hbm_bytes_per_s"],
                     flops / peak["bf16_flops_per_s"])
    return 100.0 * least / spent
