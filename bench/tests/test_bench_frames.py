"""The frame driver end to end on the CPU at smoke sizes: a sound run is
correct (the SC reference reproduces the program's features exactly),
and a broken tail makes it incorrect.  The frame cell is kept out of
``BENCHMARK.json`` (a fault of the program on the TPU), so each test adds
it back."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests.smoke import SEED, smoke_run


FRAMES = "lenet5-sc-frames-backlog"


def test_frames_smoke(add_cell):
    add_cell(FRAMES)
    line = smoke_run(FRAMES)
    assert line["correct"] is True
    assert line["checks"]["max_logit_gap"]["value"] == 0.0
    assert line["checks"]["max_logit_rel_err"]["value"] < 1e-6
    assert line["failed"] == 0 and line["attempted"] % 128 == 0
    assert set(line["metrics"]) == {"frames_per_s", "setup_s"}


def test_reference_sc_features_equal_the_program():
    from bench import frames, weights
    from bench.reference import lenet_sc
    from repro.core.sc_layer import SCConfig
    from repro.models import lenet
    c = {"image_size": 28, "channels": 1, "conv1_filters": 32,
         "conv2_filters": 8, "ksize": 5, "dense": 8, "classes": 10}
    sc = {"bits": 4}
    p = weights.lenet_params(c, SEED)
    x = frames.pool(SEED, 8)
    want = lenet._maxpool(lenet.first_layer(
        p, jnp.asarray(x, jnp.float32) / 255.0, mode="sc",
        sc_cfg=SCConfig(bits=4, adder="tff")))
    got = lenet_sc.sc_features(c, sc, p["conv1"]["w"], jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _answer_altered(monkeypatch):
    from repro.serve.gateway import frontend
    orig = frontend.gateway_stage
    monkeypatch.setattr(frontend, "gateway_stage",
                        lambda p, x, s: jnp.roll(orig(p, x, s), 1, axis=-1))


def _half_batch(monkeypatch):
    from repro.serve.gateway import frontend
    orig = frontend.gateway_stage

    def stage(p, x, s):
        logits = orig(p, x[:x.shape[0] // 2], s)
        rest = jnp.broadcast_to(logits.mean(0), logits.shape)
        return jnp.concatenate([logits, rest])
    monkeypatch.setattr(frontend, "gateway_stage", stage)


def _patches_in_bf16(monkeypatch):
    """The program's fault on the TPU: its im2col, a convolution at the
    default precision, rounds the pixels to bfloat16 before they are
    quantized to stream levels."""
    from repro.core import sc_layer
    orig = sc_layer.extract_patches
    monkeypatch.setattr(
        sc_layer, "extract_patches",
        lambda x, k, padding="SAME": orig(
            x.astype(jnp.bfloat16).astype(jnp.float32), k, padding))


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch,
                                   _patches_in_bf16])
def test_fault_makes_the_frame_cell_incorrect(monkeypatch, add_cell, fault):
    add_cell(FRAMES)
    fault(monkeypatch)
    line = smoke_run(FRAMES)
    assert line["correct"] is False
