"""Per-architecture smoke tests: reduced same-family config, one forward /
train-grad step on CPU, asserting output shapes and no NaNs; plus one
prefill+decode round per family."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serve import engine


def _batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    if cfg.family == "encdec":
        batch["enc_embed"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.enc_len, cfg.d_model)), jnp.bfloat16)
    if cfg.family == "vlm":
        batch["vision_embed"] = jnp.asarray(
            rng.normal(0, 1, (B, cfg.n_vision_tokens, cfg.d_model)),
            jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_forward_and_grad(arch):
    cfg = configs.smoke_config(arch)
    params, specs = lm.init(jax.random.key(0), cfg, {})
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: not isinstance(x, dict))
    batch = _batch(cfg)

    def loss_fn(p):
        return lm.forward(cfg, p, batch)[0]

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
    assert np.isfinite(float(loss)), arch
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_prefill_decode(arch):
    cfg = configs.smoke_config(arch)
    params, _ = lm.init(jax.random.key(0), cfg, {})
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    cache, logits = jax.jit(lambda p, b: engine.prefill(cfg, p, b))(
        params, batch)
    assert logits.shape == (B, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    nc, lg = jax.jit(lambda p, c, t: engine.decode_step(cfg, p, c, t))(
        params, _grow(cache, 8), batch["tokens"][:, :1])
    assert lg.shape == (B, cfg.vocab_padded)
    assert np.isfinite(np.asarray(lg, np.float32)).all(), arch
    assert int(nc["len"]) == S + 1


def _grow(cache, n):
    """A prefill cache with ``n`` free positions on each attention
    cache's sequence axis, so decode has somewhere to write."""
    grown = dict(cache)
    for k in ("k", "v", "kx_self", "vx_self", "latent"):
        if k in grown:
            pad = [(0, 0)] * grown[k].ndim
            pad[engine.seq_axis(k)] = (0, n)
            grown[k] = jnp.pad(grown[k], pad)
    return grown


@pytest.mark.parametrize("arch", ["deepseek_moe_16b", "moonshot_v1_16b_a3b"])
def test_batched_decode_matches_one_lane_calls(arch):
    """Decode routes each lane's token as an expert dispatch group of its
    own, so a lane's logits do not depend on the other lanes: a B=3
    ``decode_step`` matches three B=1 calls (float32 params)."""
    cfg = dataclasses.replace(configs.smoke_config(arch),
                              param_dtype="float32")
    params, _ = lm.init(jax.random.key(2), cfg, {})
    B, S = 3, 12
    batch = _batch(cfg, B, S, seed=5)
    cache = _grow(engine.prefill(cfg, params, batch)[0], 4)
    tokens = batch["tokens"][:, ::-1][:, :1]
    _, logits = engine.decode_step(cfg, params, cache, tokens)
    for b in range(B):
        lane = {k: a if k == "len" else a[:, b:b + 1]
                for k, a in cache.items()}
        _, one = engine.decode_step(cfg, params, lane, tokens[b:b + 1])
        np.testing.assert_allclose(np.asarray(logits[b]),
                                   np.asarray(one[0]), rtol=1e-5,
                                   atol=1e-5)
        assert int(jnp.argmax(logits[b])) == int(jnp.argmax(one[0]))


@pytest.mark.parametrize("arch", configs.ARCHS)
def test_full_config_abstract(arch):
    """The FULL published config builds abstractly (shapes only) and its
    parameter count matches the published scale."""
    cfg = configs.config(arch)
    params, specs = lm.init(None, cfg, {"data": 16, "model": 16},
                            abstract=True)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    expected = {
        "llama3_405b": 405e9, "starcoder2_15b": 15e9, "deepseek_67b": 67e9,
        "stablelm_3b": 2.8e9, "whisper_medium": 0.8e9,
        "llama32_vision_90b": 90e9, "rwkv6_7b": 7.5e9, "hymba_1_5b": 1.6e9,
        "deepseek_moe_16b": 16e9, "moonshot_v1_16b_a3b": 15.96e9,
    }[arch]
    assert 0.8 * expected < n < 1.25 * expected, (arch, n)


def test_decode_matches_forward_logits():
    """Incremental decode reproduces the teacher-forced forward logits
    (f32 params for a tight tolerance)."""
    cfg = configs.smoke_config("stablelm_3b")
    cfg = type(cfg)(**{**cfg.__dict__, "param_dtype": "float32"})
    params, _ = lm.init(jax.random.key(1), cfg, {})
    rng = np.random.default_rng(0)
    B, S = 1, 12
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    # forward logits at position t for all t: prefill of t+1 tokens
    cache, logits_prefill = engine.prefill(cfg, params, {"tokens": toks})
    # decode path: prefill S-1 then decode token S-1
    cache2, _ = engine.prefill(cfg, params, {"tokens": toks[:, :-1]})
    grown = dict(cache2)
    for k in ("k", "v"):
        pad = [(0, 0)] * grown[k].ndim
        pad[-3] = (0, 4)
        grown[k] = jnp.pad(grown[k], pad)
    _, logits_decode = engine.decode_step(cfg, params, grown, toks[:, -1:])
    np.testing.assert_allclose(np.asarray(logits_decode),
                               np.asarray(logits_prefill),
                               rtol=2e-4, atol=2e-4)
