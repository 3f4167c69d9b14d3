"""Plain float32 reference of the latent-attention MoE family as published
(Moonlight-16B-A3B, DeepSeek-V3 modelling code).

Pre-norm blocks of RMSNorm.  Attention is latent (MLA) in its expanded,
non-absorbed form: queries ``x @ wq`` (no query LoRA) split into a 128-wide
part and a 64-wide part that takes RoPE; ``x @ w_kv_a`` gives the
512-wide latent, RMS-normed, and one 64-wide key part that takes RoPE and
is shared by every head; the latent times ``w_kv_b`` gives each head's
128-wide key part and 128-wide value; scores scaled by 1/sqrt(192), causal
softmax, ``wo``.  Layer 0's feed-forward is a dense SwiGLU; every later
layer's is a mixture of experts: sigmoid router scores in float32, the
top-k of scores plus the correction bias select, the selected unbiased
scores weight, renormalised and times ``routed_scaling``; every expert is
computed for every token and masked by that choice; the shared experts are
one SwiGLU of their summed width.  A final RMSNorm and an untied head.

Plain ``jax.numpy`` at ``precision=HIGHEST``, no cache, no kernels, no
batching of lanes: the whole sequence in one pass, layer by layer, each
layer's weights made anew from the seed (``bench/weights_mla_moe.py``).
Departures from the published code, all under the configuration's
``assumed``: RoPE in rotate-half form where DeepSeek rotates interleaved
pairs (a fixed permutation of the random weights' columns), every norm
scale 1, and the correction bias drawn at random.

``served_gaps`` is the comparison that decides ``correct``, as in
``bench/reference/decoder.py``; the control ``f8`` runs the same pass
with every matrix product's operands rounded to float8 e4m3.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights, weights_mla_moe as wm
from bench.reference.decoder import CONTROLS, HI, _mm, _rms, _rope, \
    _round_up

__all__ = ["CONTROLS", "logits", "served_gaps"]
ROW_CHUNK = 512          # served tokens whose logits are formed at once


def _swiglu(h, w_gate, w_in, w_out, low):
    return _mm(jax.nn.silu(_mm(h, w_gate, low)) * _mm(h, w_in, low), w_out,
               low)


def _mla(m, w, h, low):
    n, s, _ = h.shape
    H, r = m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    q = _mm(h, w["wq"], low).reshape(n, s, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m["rope_theta"])],
                        -1)
    kv = _mm(h, w["w_kv_a"], low)
    c = _rms(kv[..., :r], m["norm_eps"])
    k_pe = _rope(kv[..., None, r:], m["rope_theta"])          # (n, s, 1, dr)
    kvb = _mm(c, w["w_kv_b"], low).reshape(n, s, H, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe, (n, s, H, dr))], -1)
    att = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / np.sqrt(dn + dr)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", att, kvb[..., dn:], precision=HI)
    return _mm(o.reshape(n, s, H * dv), w["wo"], low)


def _moe(m, w, h, low):
    scores = jax.nn.sigmoid(_mm(h, w["w_router"], low))       # (n, s, E)
    _, top = jax.lax.top_k(scores + w["router_bias"], m["top_k"])
    gate = jnp.take_along_axis(scores, top, -1)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    gate = gate * m["routed_scaling"]
    gates = jnp.sum(jax.nn.one_hot(top, m["n_experts"]) * gate[..., None],
                    -2)                                       # (n, s, E)

    def expert(acc, e):
        wg, wi, wo, g = e
        return acc + g[..., None] * _swiglu(h, wg, wi, wo, low), None
    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             (w["w_gate"], w["w_in"], w["w_out"],
                              jnp.moveaxis(gates, -1, 0)))
    return routed + _swiglu(h, w["shared_gate"], w["shared_in"],
                            w["shared_out"], low)


def _layer(m, w, x, low):
    x = x + _mla(m, w, _rms(x, m["norm_eps"]), low)
    h = _rms(x, m["norm_eps"])
    if "w_router" in w:
        return x + _moe(m, w, h, low)
    return x + _swiglu(h, w["mlp.w_gate"], w["mlp.w_in"], w["mlp.w_out"],
                       low)


def _hidden(m, root, tokens, lows) -> list:
    """The last layer's output (N, S, d) of ``tokens`` (N, S), one pass per
    entry of ``lows`` (True: every product in float8)."""
    x = jax.jit(lambda key, t: wm.leaf(m, key, "embed")
                .astype(jnp.float32)[t])(root, jnp.asarray(tokens))
    names = {False: wm.ATTN + wm.DENSE, True: wm.ATTN + wm.MOE}
    layer_w = {moe: jax.jit(lambda key, l, names=names[moe]: {
        name: wm.leaf(m, key, name, l).astype(jnp.float32)
        for name in names}) for moe in (False, True)}
    step = jax.jit(lambda w, xi, low: _layer(m, w, xi, low),
                   static_argnums=(2,))
    xs = [x] * len(lows)
    for layer in range(m["n_layers"]):
        w = layer_w[layer > 0](root, layer)
        xs = [step(w, xi, low) for xi, low in zip(xs, lows)]
        del w
    return xs


def logits(m: dict, seed: int, tokens) -> np.ndarray:
    """The reference's float32 logits (S, vocab padded) at every position
    of one sequence: what the tests hold the served path's prefill and
    decode logits to."""
    root = weights.base_key(seed)
    x = _hidden(m, root, np.asarray(tokens, np.int32)[None], (False,))[0]
    head = wm.leaf(m, root, "lm_head").astype(jnp.float32)
    return np.asarray(_mm(_rms(x[0], m["norm_eps"]), head, False))


def served_gaps(m: dict, seed: int, served: list[tuple[np.ndarray, list]],
                control: str | None = None) -> dict:
    """``served``: (prompt, generated tokens) per request.  Returns the gap
    of every served token (``"gaps"``) and, with ``control="f8"``, the gap
    of the token the float8 pass puts first at each of those positions
    (``"control_gaps"``): how far the reference's logit of the token lies
    below the reference's best logit at that position.

    Requests are padded to a multiple of 4 and sequences to a multiple of
    256 positions, and the seed's key is an argument of every program, so
    the programs compile once per cell and then come from the cache."""
    if m["family"] != "moe" or m["router_score"] != "sigmoid" or \
            not m["kv_lora_rank"] or m["tie_embeddings"]:
        raise ValueError("this reference covers untied latent-attention "
                         "MoE models with the sigmoid router only")
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}; have {CONTROLS}")
    control = control is not None
    seqs = [np.concatenate([np.asarray(p), np.asarray(g[:-1], np.int32)])
            for p, g in served]
    n, s = _round_up(len(seqs), 4), _round_up(max(len(t) for t in seqs), 256)
    tokens = np.zeros((n, s), np.int32)
    k = sum(len(g) for _, g in served)
    rows = np.zeros(_round_up(k, ROW_CHUNK), np.int32)
    cols = np.zeros_like(rows)
    want = np.zeros_like(rows)
    j = 0
    for i, ((p, g), t) in enumerate(zip(served, seqs)):
        tokens[i, :len(t)] = t
        for q, tok in enumerate(g):
            rows[j], cols[j], want[j] = i, len(p) - 1 + q, int(tok)
            j += 1
    root = weights.base_key(seed)
    xs = _hidden(m, root, tokens, (False, True) if control else (False,))

    @jax.jit
    def gaps(key, xr, xc, rows, cols, want):
        head = wm.leaf(m, key, "lm_head").astype(jnp.float32)

        def chunk(c):
            r, q, tok = c
            ref = _mm(_rms(xr[r, q], m["norm_eps"]), head, False)
            best = jnp.max(ref, -1)
            at = jnp.arange(r.shape[0])
            out = [best - ref[at, tok]]
            if xc is not None:
                pick = jnp.argmax(_mm(_rms(xc[r, q], m["norm_eps"]), head,
                                      True), -1)
                out.append(best - ref[at, pick])
            return out
        return [g.reshape(-1) for g in jax.lax.map(
            chunk, tuple(a.reshape(-1, ROW_CHUNK) for a in (rows, cols,
                                                           want)))]
    got = gaps(root, xs[0], xs[1] if control else None, jnp.asarray(rows),
               jnp.asarray(cols), jnp.asarray(want))
    out = {"gaps": np.asarray(got[0])[:k]}
    if control:
        out["control_gaps"] = np.asarray(got[1])[:k]
    return out
