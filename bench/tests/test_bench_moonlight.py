"""The Moonlight-16B-A3B cell on the CPU at smoke sizes: the served path
against the plain reference (``bench/reference/mla_moe.py``), the cell's
runs through the harness, its traced counters, and its control.

The smoke model keeps every mechanism of the configuration (dense layer
0, latent attention, sigmoid routing with a correction bias, shared
experts) at test widths, in float32: at such widths one bfloat16 rounding
of a router score flips a token's choice of expert (a widest gap of 0.52
in bfloat16 against 0.0 in float32, same seed, on the CPU), so the
program is held to the reference's mathematics in float32 here and in
bfloat16 at published widths on the chip.
"""
import dataclasses

import numpy as np
import pytest

from bench.drivers import lm_open_loop_moe
from bench.reference import mla_moe
from bench.tests.smoke import SEED, SMOKE_DECODE_MIX, smoke_run

CELL = "moonlight16b-reasoning-backlog"
CHECK = lm_open_loop_moe.CHECK
MODEL = {"family": "moe", "n_layers": 3, "d_model": 64, "n_heads": 4,
         "n_kv_heads": 4, "d_head": 24, "d_ff": 128, "vocab": 512,
         "mlp_type": "swiglu", "rope_theta": 50000.0,
         "norm_type": "rmsnorm", "norm_eps": 1e-5, "tie_embeddings": False,
         "param_dtype": "float32", "n_experts": 8, "top_k": 2,
         "n_shared": 1, "d_expert": 32, "first_dense_ff": 128,
         "router_score": "sigmoid", "routed_scaling": 2.446,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "moe_dropless_prefill": True}
SMOKE = {"model": MODEL,
         "serve": {"paged": True, "backend": "xla", "block_size": 8,
                   "n_slots": 4, "max_len": 64, "num_blocks": None}}
# the configuration's depth and routing (dense layer 0 and 8 MoE layers,
# 64 experts top-6, 2 shared) at a test's width, with outputs long enough
# that the sample compares some hundreds of tokens: the float8 control's
# rounding then moves a share of tokens like the one on the chip
DEEP = dict(SMOKE, model=dict(
    MODEL, n_layers=9, d_model=256, d_head=48, d_ff=512, vocab=8192,
    n_experts=64, top_k=6, n_shared=2, d_expert=64, first_dense_ff=512,
    kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32), serve=dict(SMOKE["serve"], max_len=128))
LONG_MIX = dict(SMOKE_DECODE_MIX, check_requests=8,
                output_len={"dist": "uniform_int", "lo": 56, "hi": 72})


def test_reasoning_backlog_smoke():
    line = smoke_run(CELL, config=SMOKE, mix=SMOKE_DECODE_MIX)
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
    assert line["metrics"]["tokens_per_s"]["value"] > 0


def test_reasoning_backlog_traced_reads_moe_counters():
    """A traced run reads the expert-FFN rows per router pick from the
    adapter's totals: every lane fills each of the 8 experts' buffers of
    2 rows (the one-token group's capacity), where its 2 picks need 2, so
    8 rows a pick when every lane is active, more when one idles."""
    line = smoke_run(CELL, trace=True, config=SMOKE, mix=SMOKE_DECODE_MIX)
    assert line["correct"] is True
    m = line["metrics"]
    assert m["moe_rows_per_pick.moonlight"]["value"] >= 8.0
    assert m["tick_ms.moonlight"]["value"] > 0
    # no TPU here: no device metric is reported
    assert "moe_decode_mfu.moonlight" not in m
    assert "latent_attn_roofline.moonlight" not in m


@pytest.mark.parametrize("control", mla_moe.CONTROLS)
def test_control_fails_the_limit(control):
    line = smoke_run(CELL, control=control, config=DEEP, mix=LONG_MIX,
                     seconds=3.0)
    assert line["correct"] is False
    assert set(line["checks"]) == {CHECK}
    limit = line["checks"][CHECK]["limit"]
    assert line["checks"][CHECK]["value"] > limit
    assert line["info"]["program"][CHECK] <= limit


def test_wide_share_counts_tokens_above_the_gap():
    """The cell's check: the share of compared tokens whose gap exceeds
    ``max_logit_gap``; a gap equal to it is not wide, and a run that
    compared nothing reads 1.0, above every limit."""
    gaps = np.asarray([0.0, 0.0, 0.25, 0.26, 3.0])
    assert lm_open_loop_moe.wide_share(gaps, 0.25) == 0.4
    assert lm_open_loop_moe.wide_share(gaps, 3.0) == 0.0
    assert lm_open_loop_moe.wide_share(np.zeros(0), 0.25) == 1.0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_served_logits_match_the_reference(backend):
    """Prefill, then decode through the paged gateway (``ServeSpec`` ->
    ``make_gateway`` -> the batcher's adapter) on the benchmark's seeded
    weights: the first token is the reference's best at the prompt's end,
    and every decode tick's logits are the reference's at that position.
    Tolerance 2e-4: float32 on both sides; the program attends in the
    absorbed form over cached latent rows (the kernel in interpret mode
    under "pallas"), the reference in the expanded form at HIGHEST
    precision, so the two differ by summation order only."""
    from repro import configs
    from repro.serve.spec import ServeSpec, make_gateway
    from bench import weights_mla_moe
    cfg = dataclasses.replace(configs.config("moonshot-v1-16b-a3b"), **MODEL)
    params = weights_mla_moe.params(MODEL, SEED)
    gw = make_gateway(cfg, params, ServeSpec(
        paged=True, backend=backend, n_slots=2, max_len=32, block_size=4))
    ad = gw.batcher.adapter
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, MODEL["vocab"], 7).astype(np.int32)
    forced = rng.integers(0, MODEL["vocab"], 9).astype(np.int32)
    want = mla_moe.logits(MODEL, SEED, np.concatenate([prompt, forced]))
    assert ad.insert(0, prompt, max_new=12) == int(np.argmax(want[6]))
    active = np.asarray([True, False])
    for i, tok in enumerate(forced):
        ad.decode(np.asarray([tok, 0], np.int32), active)
        np.testing.assert_allclose(np.asarray(ad.last_logits)[0],
                                   want[len(prompt) + i], rtol=2e-4,
                                   atol=2e-4)


def test_costs_count_the_programs_active_parameters():
    """``costs_mla.token_params`` at the published widths is the program's
    own count of a token's active parameters (``lm.active_params``: every
    weight but the 64 - 6 unpicked experts of each MoE layer) less what a
    token reads without multiplying: the embedding row, the norm scales
    and the router's bias."""
    import json
    import pathlib
    from repro import configs
    from repro.models import lm
    from bench import costs_mla
    root = pathlib.Path(__file__).resolve().parents[1]
    m = json.loads((root / "configs" / "moonlight-16b-a3b.json")
                   .read_text())["model"]
    cfg = dataclasses.replace(configs.config("moonshot-v1-16b-a3b"), **m)
    params = lm.init(None, cfg, abstract=True)[0]
    unread = [params["embed"], params["final_norm"]["scale"],
              params["blocks"]["moe"]["router_bias"]]
    for blk in ("dense0", "blocks"):
        unread += [params[blk]["ln1"]["scale"], params[blk]["ln2"]["scale"],
                   params[blk]["attn"]["kv_norm"]["scale"]]
    assert costs_mla.token_params(m) == lm.active_params(cfg) - sum(
        int(np.prod(a.shape)) for a in unread)
    # the latent read: one 576-wide row per live position, every layer
    flops, nbytes = costs_mla.latent_attn_cost(m, [0, 9])
    rows = 1 + 10
    assert nbytes == 9 * 2 * (rows * 576 + 2 * 16 * (2 * 512 + 64))
    assert flops == 9 * 2 * 16 * (2 * 512 + 64) * rows
