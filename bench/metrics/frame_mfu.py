"""Whole step: LeNet-5 FLOP/s of the traced window over the chip's bf16
peak, %, at two operations per multiply-accumulate and the SC first
layer counted once at its binary-equivalent MACs."""
from bench import costs


def read(run):
    c, tr, peak = run["counters"], run["trace"], run["peak"]
    if not c.get("frames") or peak is None or tr.window_s <= 0:
        return None
    flops = 2 * costs.lenet_macs(c["lenet"]) * c["frames"]
    return 100.0 * flops / tr.window_s / peak["bf16_flops_per_s"]
