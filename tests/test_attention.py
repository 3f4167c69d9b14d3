import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # bare env: deterministic sweep fallback
    from _hypothesis_compat import given, settings, st

from repro.nn import attention


def _naive(q, k, v, causal, window, q_offset=0):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = Hq // Hkv
    k = np.repeat(np.asarray(k, np.float64), rep, axis=2)
    v = np.repeat(np.asarray(v, np.float64), rep, axis=2)
    q = np.asarray(q, np.float64)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    qpos = q_offset + np.arange(Sq)[:, None]
    kpos = np.arange(Sk)[None, :]
    mask = np.ones((Sq, Sk), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = np.where(mask[None, None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


@given(st.integers(1, 2), st.sampled_from([3, 8, 17, 33]),
       st.sampled_from([(2, 1), (4, 2), (4, 4)]), st.booleans(),
       st.sampled_from([0, 5]), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_chunked_matches_naive(B, S, heads, causal, window, seed):
    Hq, Hkv = heads
    D = 8
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    out = attention.attend_chunked(q, k, v, causal=causal, window=window,
                                   q_chunk=8, kv_chunk=8)
    want = _naive(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-4, atol=2e-4)


def test_chunked_with_q_offset():
    """Chunked prefill continuation: q block positioned after the cache."""
    rng = np.random.default_rng(0)
    B, Sq, Sk, H, D = 1, 4, 12, 2, 8
    q = jnp.asarray(rng.normal(0, 1, (B, Sq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, Sk, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, Sk, H, D)), jnp.float32)
    out = attention.attend_chunked(q, k, v, causal=True, q_offset=8,
                                   q_chunk=4, kv_chunk=4)
    want = _naive(q, k, v, True, 0, q_offset=8)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [0, 3])
def test_decode_matches_naive(window):
    rng = np.random.default_rng(1)
    B, Smax, Hq, Hkv, D = 2, 10, 4, 2, 8
    cache_len = 7
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(0, 1, (B, Smax, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(0, 1, (B, Smax, Hkv, D)), jnp.float32)
    out = attention.attend_decode(q, kc, vc, jnp.int32(cache_len),
                                  window=window)
    # naive over the valid prefix with the window
    lo = max(0, cache_len - window) if window else 0
    want = _naive(q, kc[:, lo:cache_len], vc[:, lo:cache_len], False, 0)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 5), (False, 0)])
def test_flash_custom_vjp_gradients(causal, window):
    """The recompute-based backward equals autodiff-through-naive-attention
    gradients (the §Perf iteration-1 optimization must be exact)."""
    rng = np.random.default_rng(3)
    B, S, Hq, Hkv, D = 1, 12, 4, 2, 8
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    tgt = jnp.asarray(rng.normal(0, 1, (B, S, Hq, D)), jnp.float32)

    def loss_ours(q, k, v):
        o = attention.attend_chunked(q, k, v, causal=causal, window=window,
                                     q_chunk=4, kv_chunk=4)
        return jnp.sum((o - tgt) ** 2)

    def _naive_jax(q, k, v):
        rep = Hq // Hkv
        kk = jnp.repeat(k, rep, axis=2)
        vv = jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(D)
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        m = jnp.ones((S, S), bool)
        if causal:
            m &= qpos >= kpos
        if window:
            m &= (qpos - kpos) < window
        s = jnp.where(m[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    def loss_naive(q, k, v):
        return jnp.sum((_naive_jax(q, k, v) - tgt) ** 2)

    g_ours = jax.grad(loss_ours, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ours, g_naive):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_decode_consistent_with_chunked_last_row():
    """decode(q_t | cache) == last row of full chunked attention."""
    rng = np.random.default_rng(2)
    B, S, Hq, Hkv, D = 1, 9, 4, 2, 8
    q = jnp.asarray(rng.normal(0, 1, (B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    full = attention.attend_chunked(q, k, v, causal=True, q_chunk=4,
                                    kv_chunk=4)
    dec = attention.attend_decode(q[:, -1:], k, v, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(dec[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_absorbed_latent_decode_matches_expanded_form():
    """Latent attention's decode form (``w_kv_b``'s key half folded into
    the query, its value half applied after attending over the cached
    latent rows) equals the expanded prefill form (the latent projected up
    to per-head keys and values) at a small size, in float32; and decode
    writes the row prefill computes."""
    import dataclasses
    from repro import configs
    from repro.models import lm
    from repro.serve import engine
    cfg = dataclasses.replace(configs.smoke_config("moonshot_v1_16b_a3b"),
                              param_dtype="float32")
    params, _ = lm.init(jax.random.key(3), cfg, {})
    p = jax.tree.map(lambda a: a[0], params["blocks"]["attn"])
    S = 10
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1,
                                                    (1, S, cfg.d_model)),
                    jnp.float32)
    full, rows = lm._mla_apply(cfg, p, x, jnp.arange(S)[None])
    cache = jnp.zeros((1, 16, cfg.latent_width)).at[:, :S - 1].set(
        rows[:, :S - 1])
    out, (cache,) = engine._dense_attend(cfg, jnp.int32(S - 1))(
        p, x[:, S - 1:], (cache,), 0)
    np.testing.assert_allclose(np.asarray(out[:, 0]),
                               np.asarray(full[:, S - 1]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache[:, :S]), np.asarray(rows),
                               rtol=1e-6, atol=1e-6)
