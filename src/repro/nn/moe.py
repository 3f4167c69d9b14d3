"""Fine-grained mixture-of-experts (DeepSeekMoE-style: shared experts + many
small routed experts, top-k with renormalized gates).

Dispatch is the GShard grouped-einsum formulation: tokens are split into
groups of ``group_size``; each group routes into per-expert capacity buffers
via a one-hot dispatch tensor.  Groups shard over the data axis and experts
over the model axis (EP), so the dispatch/combine einsums induce exactly the
expected all-to-all pattern under pjit.  An alternative sort-based dispatch
(``impl="sort"``) exists for the perf study.

Capacity overflow drops tokens (standard GShard semantics); an auxiliary
load-balance loss is returned for training.

Two routers: ``score="softmax"`` (DeepSeekMoE: softmax over the experts,
top-k of the probabilities, renormalised) and ``score="sigmoid"``
(DeepSeek-V3's ``noaux_tc``, Moonlight: sigmoid scores in float32, the top-k
of the scores *plus* a per-expert correction bias selects, the unbiased
scores of the selected experts weight, renormalised and multiplied by
``routed_scaling``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.dist.sharding import hint


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    group_size: int = 2048
    aux_loss_weight: float = 0.01
    impl: str = "einsum"   # "einsum" | "sort"
    # dropless: capacity = the whole group, so routing never drops a token.
    # Serving prefill uses this (a token's output must not depend on which
    # other prompts share its dispatch group — the prerequisite for resuming
    # a prompt from a cached prefix); training keeps GShard drop semantics.
    dropless: bool = False
    score: str = "softmax"           # "softmax" | "sigmoid" (biased top-k)
    routed_scaling: float = 1.0      # sigmoid router's output scale


def capacity(cfg: MoEConfig, group_tokens: int) -> int:
    if cfg.dropless:
        return -(-group_tokens // 4) * 4    # every token always fits
    c = int(group_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(cfg.top_k, -(-c // 4) * 4)   # round up to 4 for layout


def expert_rows(cfg: MoEConfig, group_tokens: int) -> int:
    """Rows the expert FFN computes for one dispatch group of
    ``group_tokens`` tokens: every expert's capacity buffer, filled or not
    (the ``(E, C)`` buffers both dispatches build)."""
    return cfg.n_experts * capacity(cfg, group_tokens)


def router(x, w_router, cfg: MoEConfig, bias=None):
    """x: (G, T, d) -> (weights (G,T,k), experts (G,T,k) int32, aux loss).

    ``bias`` (E,): the sigmoid router's correction bias, added to the
    scores for the selection only."""
    logits = jnp.einsum("gtd,de->gte", x.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    if cfg.score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                 cfg.top_k)
        top_w = jnp.take_along_axis(scores, top_e, axis=-1)
        top_w = top_w / (jnp.sum(top_w, -1, keepdims=True) + 1e-20)
        top_w = top_w * cfg.routed_scaling
        probs = scores / jnp.sum(scores, -1, keepdims=True)
        return top_w, top_e, _balance_loss(probs, top_e, cfg)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return top_w, top_e, _balance_loss(probs, top_e, cfg)


def _balance_loss(probs, top_e, cfg: MoEConfig):
    # load-balance aux loss (Switch-style): E * mean(density * mean_prob)
    density = jnp.mean(
        jax.nn.one_hot(top_e, cfg.n_experts, dtype=jnp.float32), axis=(1, 2))
    mean_prob = jnp.mean(probs, axis=1)
    return cfg.n_experts * jnp.mean(jnp.sum(density * mean_prob, axis=-1))


def _dispatch_einsum(x, top_w, top_e, cfg: MoEConfig, params):
    G, T, d = x.shape
    C = capacity(cfg, T)
    E = cfg.n_experts
    # position of each (token, k) within its expert's buffer
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)          # (G,T,k,E)
    pos = jnp.cumsum(onehot.reshape(G, T * cfg.top_k, E), axis=1)
    pos = pos.reshape(G, T, cfg.top_k, E) - 1                    # 0-based slot
    in_cap = (pos < C) & (onehot > 0)
    # Accumulate dispatch/combine per routing choice so the peak intermediate
    # stays (G,T,E,C) — never (G,T,k,E,C).
    dispatch = jnp.zeros((G, T, E, C), x.dtype)
    combine = jnp.zeros((G, T, E, C), x.dtype)
    for i in range(cfg.top_k):
        e_oh = (onehot[:, :, i, :] * in_cap[:, :, i, :]).astype(x.dtype)
        p_i = jnp.sum(pos[:, :, i, :] * onehot[:, :, i, :], axis=-1)  # (G,T)
        p_oh = jax.nn.one_hot(p_i, C, dtype=x.dtype)                  # (G,T,C)
        contrib = jnp.einsum("gte,gtc->gtec", e_oh, p_oh)
        dispatch = dispatch + contrib
        combine = combine + contrib * top_w[:, :, i, None, None].astype(x.dtype)
    xin = jnp.einsum("gtec,gtd->gecd", dispatch, x)              # (G,E,C,d)
    xin = hint(xin, "batch", "model", None, None)                # EP all-to-all
    h = _expert_ffn(xin, params)                                 # (G,E,C,d)
    h = hint(h, "batch", "model", None, None)
    return jnp.einsum("gtec,gecd->gtd", combine, h)


def _dispatch_sort(x, top_w, top_e, cfg: MoEConfig, params):
    """Sort-based dispatch: argsort tokens by expert, scatter into (E*C, d)
    buffers.  Fewer FLOPs than the one-hot einsums; relies on SPMD handling
    of gather/scatter (perf-study alternative)."""
    G, T, d = x.shape
    C = capacity(cfg, T)
    E = cfg.n_experts
    k = cfg.top_k
    flat_e = top_e.reshape(G, T * k)
    order = jnp.argsort(flat_e, axis=1)                          # stable
    tok = order // k
    sorted_e = jnp.take_along_axis(flat_e, order, axis=1)
    # position within expert = running index minus start of expert segment
    seg_start = jnp.cumsum(
        jax.nn.one_hot(sorted_e, E, dtype=jnp.int32), axis=1) - 1
    pos = jnp.take_along_axis(seg_start, sorted_e[..., None], axis=2)[..., 0]
    slot = sorted_e * C + pos
    ok = pos < C
    slot = jnp.where(ok, slot, E * C)                            # overflow bin
    xg = jnp.take_along_axis(x, tok[..., None], axis=1)          # (G,T*k,d)
    buf = jnp.zeros((G, E * C + 1, d), x.dtype)
    buf = jax.vmap(lambda b, s, v: b.at[s].add(v))(buf, slot, xg)
    h = _expert_ffn(buf[:, :E * C].reshape(G, E, C, d), params)
    h = h.reshape(G, E * C, d)
    hg = jnp.take_along_axis(h, jnp.minimum(slot, E * C - 1)[..., None],
                             axis=1)
    w = jnp.take_along_axis(top_w.reshape(G, T * k), order, axis=1)
    hg = hg * (w * ok.astype(w.dtype))[..., None]
    out = jnp.zeros((G, T, d), x.dtype)
    return jax.vmap(lambda o, t, v: o.at[t].add(v))(out, tok, hg)


def _expert_ffn(xin, params):
    """xin: (G, E, C, d) -> SwiGLU per expert with weights (E, d, f)/(E, f, d)."""
    g = hint(jnp.einsum("gecd,edf->gecf", xin, params["w_gate"]),
             "batch", "model", None, None)
    h = hint(jnp.einsum("gecd,edf->gecf", xin, params["w_in"]),
             "batch", "model", None, None)
    a = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    return jnp.einsum("gecf,efd->gecd", a, params["w_out"])


def moe_ffn(x, params, cfg: MoEConfig):
    """x: (B, S, d).  Returns (out (B,S,d), aux_loss scalar).

    params: {w_router (d,E), w_gate/w_in (E,d,f), w_out (E,f,d),
             shared_gate/shared_in (d, n_shared*f), shared_out (n_shared*f, d),
             router_bias (E,) f32 for the sigmoid router}
    """
    B, S, d = x.shape
    tokens = B * S
    gs = min(cfg.group_size, tokens)
    G = tokens // gs
    assert G * gs == tokens, f"group_size {gs} must divide tokens {tokens}"
    xg = hint(x.reshape(G, gs, d), "batch", None, None)
    top_w, top_e, aux = router(xg, params["w_router"], cfg,
                               params.get("router_bias"))
    impl = {"einsum": _dispatch_einsum, "sort": _dispatch_sort}[cfg.impl]
    routed = impl(xg, top_w, top_e, cfg, params).reshape(B, S, d)
    if cfg.n_shared > 0:
        from repro.nn.mlp import swiglu
        routed = routed + swiglu(x, params["shared_gate"], params["shared_in"],
                                 params["shared_out"])
    return routed, aux
