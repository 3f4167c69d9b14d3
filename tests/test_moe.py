import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn import moe as moe_lib
from repro.nn.moe import MoEConfig


def _params(rng, E, d, f, n_shared=0):
    p = {
        "w_router": jnp.asarray(rng.normal(0, 0.5, (d, E)), jnp.float32),
        "w_gate": jnp.asarray(rng.normal(0, 0.1, (E, d, f)), jnp.float32),
        "w_in": jnp.asarray(rng.normal(0, 0.1, (E, d, f)), jnp.float32),
        "w_out": jnp.asarray(rng.normal(0, 0.1, (E, f, d)), jnp.float32),
    }
    if n_shared:
        sf = n_shared * f
        p.update(
            shared_gate=jnp.asarray(rng.normal(0, 0.1, (d, sf)), jnp.float32),
            shared_in=jnp.asarray(rng.normal(0, 0.1, (d, sf)), jnp.float32),
            shared_out=jnp.asarray(rng.normal(0, 0.1, (sf, d)), jnp.float32))
    return p


def _dense_reference(x, p, cfg):
    """Route each token through its top-k experts directly (no capacity)."""
    B, S, d = x.shape
    xt = np.asarray(x, np.float64).reshape(-1, d)
    logits = xt @ np.asarray(p["w_router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    out = np.zeros_like(xt)
    for t in range(xt.shape[0]):
        top = np.argsort(-probs[t])[: cfg.top_k]
        wsum = probs[t, top].sum()
        for e in top:
            g = xt[t] @ np.asarray(p["w_gate"][e], np.float64)
            h = xt[t] @ np.asarray(p["w_in"][e], np.float64)
            a = (g / (1 + np.exp(-g))) * h
            out[t] += (probs[t, e] / wsum) * \
                (a @ np.asarray(p["w_out"][e], np.float64))
    return out.reshape(B, S, d)


@pytest.mark.parametrize("impl", ["einsum", "sort"])
def test_moe_matches_dense_reference(impl):
    """With generous capacity (no drops) both dispatch impls equal the dense
    per-token routing computation."""
    rng = np.random.default_rng(0)
    B, S, d, E, f, k = 2, 16, 8, 4, 16, 2
    cfg = MoEConfig(n_experts=E, top_k=k, d_expert=f, capacity_factor=4.0,
                    group_size=16, impl=impl)
    x = jnp.asarray(rng.normal(0, 1, (B, S, d)), jnp.float32)
    p = _params(rng, E, d, f)
    out, aux = moe_lib.moe_ffn(x, p, cfg)
    want = _dense_reference(x, p, cfg)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-3, atol=2e-3)
    assert float(aux) > 0.5   # load-balance loss ~ O(1)


def test_impls_agree():
    rng = np.random.default_rng(1)
    B, S, d, E, f, k = 2, 32, 8, 8, 8, 2
    x = jnp.asarray(rng.normal(0, 1, (B, S, d)), jnp.float32)
    p = _params(rng, E, d, f)
    outs = []
    for impl in ("einsum", "sort"):
        cfg = MoEConfig(E, k, f, capacity_factor=8.0, group_size=32,
                        impl=impl)
        outs.append(np.asarray(moe_lib.moe_ffn(x, p, cfg)[0]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-3, atol=2e-3)


def test_capacity_drops_tokens():
    """With capacity_factor -> tiny, some tokens get zero routed output."""
    rng = np.random.default_rng(2)
    B, S, d, E, f = 1, 64, 8, 4, 8
    cfg = MoEConfig(E, 2, f, capacity_factor=0.1, group_size=64)
    x = jnp.asarray(rng.normal(0, 1, (B, S, d)), jnp.float32)
    p = _params(rng, E, d, f)
    out, _ = moe_lib.moe_ffn(x, p, cfg)
    norms = np.linalg.norm(np.asarray(out), axis=-1)
    assert (norms < 1e-6).any()          # dropped tokens exist
    assert (norms > 1e-6).any()          # but not all dropped


def test_shared_experts_added():
    rng = np.random.default_rng(3)
    B, S, d, E, f = 1, 16, 8, 4, 8
    x = jnp.asarray(rng.normal(0, 1, (B, S, d)), jnp.float32)
    p = _params(rng, E, d, f, n_shared=2)
    cfg0 = MoEConfig(E, 2, f, n_shared=0, capacity_factor=4.0, group_size=16)
    cfg2 = MoEConfig(E, 2, f, n_shared=2, capacity_factor=4.0, group_size=16)
    out0, _ = moe_lib.moe_ffn(x, p, cfg0)
    out2, _ = moe_lib.moe_ffn(x, p, cfg2)
    assert not np.allclose(np.asarray(out0), np.asarray(out2))


# ==========================================================================
# The sigmoid router (DeepSeek-V3 noaux_tc, Moonlight) and the softmax one.
# ==========================================================================

def test_sigmoid_router_matches_hand_computation():
    """Scores sigmoid(x @ w); the top-k of scores + bias select, the
    unbiased scores weight, renormalised, times ``routed_scaling``.  The
    bias changes which experts are picked but never their weights."""
    E, k = 4, 2
    cfg = MoEConfig(E, k, 8, score="sigmoid", routed_scaling=2.5)
    logits = np.asarray([2.0, 1.0, 0.5, -1.0], np.float32)
    x = jnp.asarray(logits)[None, None]                 # (G=1, T=1, d=E)
    w = jnp.eye(E, dtype=jnp.float32)                   # logits = x
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    bias = jnp.zeros((E,), jnp.float32)
    top_w, top_e, _ = moe_lib.router(x, w, cfg, bias)
    assert top_e[0, 0].tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(top_w[0, 0]),
                               2.5 * s[[0, 1]] / s[[0, 1]].sum(), rtol=1e-6)
    # a bias large enough lifts expert 3 over expert 1 in the selection;
    # its weight is still its own unbiased score
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6], jnp.float32)
    top_w, top_e, _ = moe_lib.router(x, w, cfg, bias)
    assert top_e[0, 0].tolist() == [0, 3]
    np.testing.assert_allclose(np.asarray(top_w[0, 0]),
                               2.5 * s[[0, 3]] / s[[0, 3]].sum(), rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(top_w)), 2.5, rtol=1e-6)


def _softmax_router_before_sigmoid(x, w_router, cfg):
    """The softmax router as it stood before the sigmoid one joined it."""
    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    density = jnp.mean(
        jax.nn.one_hot(top_e, cfg.n_experts, dtype=jnp.float32), axis=(1, 2))
    mean_prob = jnp.mean(probs, axis=1)
    aux = cfg.n_experts * jnp.mean(jnp.sum(density * mean_prob, axis=-1))
    return top_w, top_e, aux


def test_softmax_router_bitwise_unchanged():
    """deepseek-moe-16b's softmax path computes exactly what it did before
    the sigmoid router was added: the same weights, experts and aux loss,
    bit for bit."""
    from repro import configs
    cfg = configs.smoke_config("deepseek_moe_16b")
    mcfg = cfg.moe
    assert mcfg.score == "softmax"
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(0, 1, (2, 32, cfg.d_model)), jnp.bfloat16)
    p = _params(rng, mcfg.n_experts, cfg.d_model, mcfg.d_expert,
                mcfg.n_shared)
    got = jax.jit(lambda a, w: moe_lib.router(a, w, mcfg))(x, p["w_router"])
    want = jax.jit(lambda a, w: _softmax_router_before_sigmoid(a, w, mcfg))(
        x, p["w_router"])
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
