"""Roofline, bytes and MFU arithmetic against hand counts."""
import json
import pathlib

import pytest

from bench import costs, harness, trace_reduce

M = json.loads((pathlib.Path(harness.BENCH) / "configs" /
                "stablelm-3b.json").read_text())["model"]
PEAK = costs.peak("TPU v5 lite")


def test_peaks_table():
    assert PEAK["bf16_flops_per_s"] == 197e12
    assert PEAK["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peak("TPU v9 imaginary")


def test_stablelm_parameter_counts():
    # 4 * 2560^2 attention + 3 * 2560 * 6912 SwiGLU per layer
    assert costs.layer_matmul_params(M) == 26_214_400 + 53_084_160
    assert costs.head_params(M) == 2560 * 50304
    # with the embedding and the norms: the published 2,795,276,800
    total = 32 * costs.layer_matmul_params(M) + 2 * costs.head_params(M) \
        + 2560 * (2 * 32 + 1)
    assert total == 2_795_276_800


def test_paged_attention_cost_by_hand():
    flops, nbytes = costs.paged_attn_cost(M, [99, 0])
    rows = 100 + 1
    # QK^T and PV: 2 * 2 * heads * d_head per row, every layer
    assert flops == 4 * 32 * 80 * rows * 32
    # K and V rows of 32 heads x 80 in bf16, plus query and output rows
    assert nbytes == 32 * (rows * 2 * 32 * 80 * 2 + 2 * 32 * 80 * 2 * 2)
    # memory bound: at one row the bytes take longer than the flops
    assert nbytes / PEAK["hbm_bytes_per_s"] > flops / PEAK["bf16_flops_per_s"]


def test_decode_and_prefill_flops_by_hand():
    per_token = 2 * (32 * costs.layer_matmul_params(M)
                     + costs.head_params(M))
    assert costs.decode_flops(M, [10]) == per_token + 4 * 32 * 80 * 11 * 32
    assert costs.prefill_flops(M, 16, 2) == \
        2 * 32 * costs.layer_matmul_params(M) * 2 + \
        4 * 32 * 80 * (17 + 18) * 32
    assert costs.prefill_head_flops(M, 3) == 6 * costs.head_params(M)


def test_lenet_macs_by_hand():
    c = {"image_size": 28, "channels": 1, "conv1_filters": 32,
         "conv2_filters": 64, "ksize": 5, "dense": 512, "classes": 10}
    assert costs.lenet_macs(c) == (784 * 25 * 32 + 196 * 25 * 32 * 64
                                   + 3136 * 512 + 5120)


def _trace(ops, window=(0, 1_000_000_000), spans=()):
    host = [("bench:window", *window)] + list(spans)
    return trace_reduce.Trace(host, {"/device:TPU:0": list(ops)})


def _run(counters, tr, metric):
    return harness.reader(metric).read(
        {"counters": counters, "trace": tr, "peak": PEAK, "config": {},
         "metric": metric})


def test_roofline_and_mfu_readers_by_hand():
    lens = [[99, 0]] * 10
    counters = {"model": M, "n_slots": 16, "block_size": 16,
                "ticks": [(0.03, l) for l in lens], "prefills": [],
                "tokens": 20}
    flops, nbytes = costs.paged_attn_cost(M, [99, 0])
    least = 10 * nbytes / PEAK["hbm_bytes_per_s"]
    # ten kernel events of 2 ms each (and one op that is not the kernel)
    ops = [("%paged_decode_attention.6", i * 10_000_000,
            i * 10_000_000 + 2_000_000)
           for i in range(10)] + [("fusion.3", 500_000_000, 600_000_000)]
    tr = _trace(ops)
    got = _run(counters, tr, "paged_attn_roofline.decode")
    assert got == pytest.approx(100 * least / 0.02)
    mfu = _run(counters, tr, "decode_mfu.decode")
    want = 10 * costs.decode_flops(M, [99, 0]) / 1.0 / 197e12 * 100
    assert mfu == pytest.approx(want)
    assert _run(counters, tr, "lane_occupancy.decode") == \
        pytest.approx(100 * 2 / 16)
    assert _run(counters, tr, "tick_ms.decode") == pytest.approx(30.0)
    assert _run(counters, tr, "device_idle_share.decode") == \
        pytest.approx(100 * (1 - 0.12))


def test_readers_find_nothing_and_return_none():
    tr = trace_reduce.Trace([("bench:window", 0, 10)], {})
    empty = {"model": M, "n_slots": 16, "block_size": 16, "ticks": [],
             "prefills": [], "frames": 0, "lenet": {}}
    for name in ("paged_attn_roofline.decode", "decode_mfu.decode",
                 "lane_occupancy.decode", "tick_ms.decode",
                 "device_idle_share.decode", "prefix_hit_share.chat",
                 "prefill_ms.chat", "sc_frontend_us_per_frame.frames",
                 "frame_mfu.frames"):
        assert _run(empty, tr, name) is None, name


def test_prefix_hit_share_and_frame_readers():
    counters = {"prefills": [(0.01, 300, 256), (0.02, 100, 0)],
                "frames": 1000,
                "lenet": {"image_size": 28, "channels": 1,
                          "conv1_filters": 32, "conv2_filters": 64,
                          "ksize": 5, "dense": 512, "classes": 10}}
    tr = _trace([("fusion.1", 0, 250_000_000)],
                spans=[("bench:sensor_stage", 0, 100_000_000)])
    assert _run(counters, tr, "prefix_hit_share.chat") == \
        pytest.approx(100 * 256 / 400)
    assert _run(counters, tr, "prefill_ms.chat") == pytest.approx(15.0)
    assert _run(counters, tr, "sc_frontend_us_per_frame.frames") == \
        pytest.approx(100.0)
    assert _run(counters, tr, "frame_mfu.frames") == pytest.approx(
        100 * 2 * costs.lenet_macs(counters["lenet"]) * 1000 / 197e12)
