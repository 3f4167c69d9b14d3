"""Serving: batched prefill + single-token decode with family-specific caches.

Cache layouts (leading axis = layers, consumed/produced by lax.scan):
  attention : k/v (L, B, Smax, Hkv, Dh) + scalar "len"; latent attention
              (MLA) instead one "latent" (L, B, Smax, kv_lora_rank + rope)
  rwkv      : wkv state (L, B, H, Dh, Dh) f32 + token-shift states (L, B, d)
  hybrid    : k/v + mamba conv state (L, B, K-1, di) + ssm state (L, B, di, N)
  encdec/vlm: self k/v + precomputed cross K/V from encoder/vision tokens

KV cache sharding (``cache_specs``): batch over the DP axes; KV heads over
"model" when divisible, otherwise the *sequence* axis shards over "model"
(split-KV decode — softmax renormalization turns into an all-reduce, which
XLA inserts automatically).  That is how llama-405B's 8 KV heads decode on a
16-wide TP axis without replicating a terabyte of cache.

The decode step is O(1)-state for rwkv/hybrid-SSM paths — the reason the
long_500k cells are only assigned to those families.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (axis_if_divisible, batch_spec_axis)
from repro.models import lm
from repro.models.lm import (LMConfig, _attn_apply, _causal_conv, _maybe_remat,
                             _mlp_apply, _norm_apply, _proj, _sinusoidal,
                             _token_shift, layer_window)
from repro.nn import attention, rope, ssm
from repro.serve import kvquant


# ==========================================================================
# Cache construction (+ specs).
# ==========================================================================

def init_cache(cfg: LMConfig, batch: int, max_len: int, abstract: bool = False,
               extras: dict | None = None):
    """Abstract mode returns ShapeDtypeStructs (dry-run decode inputs)."""
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))
    L, B = cfg.n_layers, batch
    Hkv, Dh, d = cfg.n_kv_heads, cfg.d_head, cfg.d_model
    fam = cfg.family
    kv_dtype = jnp.int8 if cfg.kv_quant else cfg.dtype
    cache: dict = {"len": mk((), jnp.int32)}
    if cfg.mla:
        assert fam in ("decoder", "moe") and not cfg.kv_quant, \
            "latent attention: decoder/moe families, no int8 cache"
        cache["latent"] = mk((L, B, max_len, cfg.latent_width), cfg.dtype)
    elif fam in ("decoder", "moe", "hybrid"):
        cache["k"] = mk((L, B, max_len, Hkv, Dh), kv_dtype)
        cache["v"] = mk((L, B, max_len, Hkv, Dh), kv_dtype)
        if cfg.kv_quant:
            cache["k_scale"] = mk((L, B, max_len, Hkv, 1), jnp.float32)
            cache["v_scale"] = mk((L, B, max_len, Hkv, 1), jnp.float32)
        if fam == "hybrid":
            cache["conv"] = mk((L, B, cfg.conv_k - 1, cfg.inner), cfg.dtype)
            cache["ssm"] = mk((L, B, cfg.inner, cfg.ssm_state), jnp.float32)
    elif fam == "rwkv":
        cache["wkv"] = mk((L, B, cfg.n_heads, Dh, Dh), jnp.float32)
        cache["shift1"] = mk((L, B, d), cfg.dtype)
        cache["shift2"] = mk((L, B, d), cfg.dtype)
    elif fam == "encdec":
        cache["k"] = mk((L, B, max_len, Hkv, Dh), cfg.dtype)
        cache["v"] = mk((L, B, max_len, Hkv, Dh), cfg.dtype)
        cache["xk"] = mk((L, B, cfg.enc_len, Hkv, Dh), cfg.dtype)
        cache["xv"] = mk((L, B, cfg.enc_len, Hkv, Dh), cfg.dtype)
    elif fam == "vlm":
        k = cfg.cross_every
        G = cfg.n_layers // k
        cache["k"] = mk((G, k - 1, B, max_len, Hkv, Dh), cfg.dtype)
        cache["v"] = mk((G, k - 1, B, max_len, Hkv, Dh), cfg.dtype)
        cache["kx_self"] = mk((G, B, max_len, Hkv, Dh), cfg.dtype)
        cache["vx_self"] = mk((G, B, max_len, Hkv, Dh), cfg.dtype)
        cache["xk"] = mk((G, B, cfg.n_vision_tokens, Hkv, Dh), cfg.dtype)
        cache["xv"] = mk((G, B, cfg.n_vision_tokens, Hkv, Dh), cfg.dtype)
    else:
        raise ValueError(fam)
    return cache


# Cache keys with a (paged) sequence axis.  Everything else — rwkv/ssm
# states, conv taps, encoder/vision cross K/V — is O(1) per slot and stays
# densely slot-stacked even under the paged layout.
PAGED_SEQ_KEYS = ("k", "v", "k_scale", "v_scale", "kx_self", "vx_self",
                  "latent")


def seq_axis(key: str) -> int:
    """Sequence axis of a cache key, from the end: ``(..., B, S, heads,
    feat)`` for K/V-like keys, ``(..., B, S, width)`` for the MLA latent,
    which has no head axis (a size-1 one would be padded to a whole tile
    in the TPU's HBM layout)."""
    return -2 if key == "latent" else -3


def init_paged_arena(cfg: LMConfig, num_blocks: int, block_size: int,
                     abstract: bool = False) -> dict:
    """Block arenas for the paged KV cache (serve/kvcache/).

    Per sequence-axis cache key, the B=1 cache of ``max_len=block_size``
    with a ``num_blocks`` axis spliced in just before the batch axis —
    layer-leading, so ``arena[key][..., bid, :1, :bs]`` (via
    :func:`arena_block_axis`) is exactly one block of that key and block
    granularity / cache layout can never drift apart: both come from
    :func:`init_cache`.  The layer axis stays leading (rather than the
    block axis, as in PR 2) so the in-place decode tick can scan layers
    over per-layer ``(num_blocks, 1, bs, ...)`` slices — and so the arena
    shards over a mesh with the same leading-axes PartitionSpec shape as
    ``cache_specs`` gives the dense layout (the next ROADMAP item).

    The paged adapter pins the arena's HBM format row-major where a Pallas
    kernel touches it (``kvcache.paged.arena_formats``): head dims 80 and
    64 are not multiples of the TPU's 128 lanes, so the compiler's default
    layout puts the block axis minor-most, and every tick would relayout
    the whole arena around the kernels, which read and write it row-major.
    """
    blk = init_cache(cfg, 1, block_size, abstract=True)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))
    out = {}
    for key in PAGED_SEQ_KEYS:
        if key not in blk:
            continue
        s = blk[key].shape                       # (*layers, 1, bs, *post)
        ax = len(s) + seq_axis(key) - 1          # just before the B axis
        out[key] = mk(s[:ax] + (num_blocks,) + s[ax:], blk[key].dtype)
    return out


def arena_block_axis(a, key: str) -> int:
    """Block-id axis of an :func:`init_paged_arena` array of ``key``.

    Every paged key's block shape ends ``(B=1, bs, *post)`` with the block
    axis spliced in just before B, so it sits two axes before the
    sequence axis whatever the leading layer axes look like (one for
    decoder k/v, two for the vlm grouped layout): 5 from the end for K/V,
    4 for the latent."""
    return a.ndim + seq_axis(key) - 2


def arena_specs(cfg: LMConfig, mesh_shape: dict[str, int]):
    """PartitionSpec tree matching :func:`init_paged_arena`.

    Derived from :func:`cache_specs` the same way the arena layout is
    derived from the cache layout: the dense B=1 spec with a replicated
    block axis spliced in just before the batch axis.  KV heads shard over
    "model" when divisible (the split-KV fallback then shards the
    *block-size* axis instead, mirroring the dense sequence-axis
    fallback); the block axis itself is never sharded — slices of the
    serving mesh partition the arena by *pool*, not by splitting one
    pool's blocks (see serve/shard/)."""
    dense = cache_specs(cfg, mesh_shape, batch=1)
    out = {}
    for key in PAGED_SEQ_KEYS:
        if key not in dense:
            continue
        sp = tuple(dense[key])
        ax = len(sp) + seq_axis(key) - 1         # just before the B axis
        out[key] = P(*sp[:ax], None, *sp[ax:])
    return out


def cache_specs(cfg: LMConfig, mesh_shape: dict[str, int], batch: int):
    """PartitionSpec tree matching init_cache."""
    b = batch_spec_axis(mesh_shape, batch)
    kv_heads = axis_if_divisible("model", cfg.n_kv_heads, mesh_shape)
    seq = None if kv_heads else "model"      # split-KV fallback
    fam = cfg.family
    specs: dict = {"len": P()}
    kv = P(None, b, seq, kv_heads, None)
    if cfg.mla:
        specs["latent"] = P(None, b, None, None)
    elif fam in ("decoder", "moe", "hybrid"):
        specs["k"] = kv
        specs["v"] = kv
        if cfg.kv_quant:
            specs["k_scale"] = kv
            specs["v_scale"] = kv
        if fam == "hybrid":
            di = axis_if_divisible("model", cfg.inner, mesh_shape)
            specs["conv"] = P(None, b, None, di)
            specs["ssm"] = P(None, b, di, None)
    elif fam == "rwkv":
        h = axis_if_divisible("model", cfg.n_heads, mesh_shape)
        specs["wkv"] = P(None, b, h, None, None)
        specs["shift1"] = P(None, b, None)
        specs["shift2"] = P(None, b, None)
    elif fam == "encdec":
        specs.update(k=kv, v=kv, xk=kv, xv=kv)
    elif fam == "vlm":
        kv5 = P(None, None, b, seq, kv_heads, None)
        kv4 = P(None, b, seq, kv_heads, None)
        specs.update(k=kv5, v=kv5, kx_self=kv4, vx_self=kv4, xk=kv4, xv=kv4)
    return specs


# ==========================================================================
# Prefill.
# ==========================================================================

def prefill(cfg: LMConfig, params, batch):
    """Process a full prompt; returns (cache, last-token logits).

    batch: {"tokens": (B, S)} + family extras (enc_embed / vision_embed).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = lm.embed_tokens(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    fam = cfg.family
    cache = {"len": jnp.int32(S)}

    if fam in ("decoder", "moe"):
        if fam == "moe":
            p0 = jax.tree.map(lambda a: a[0], params["dense0"])
            x, kv0, _ = lm.decoder_block(cfg, p0, x, positions)

        def body(lp, x, idx):
            x, kv, _ = lm.decoder_block(
                cfg, lp, x, positions, window=layer_window(cfg, idx),
                moe_layer=(fam == "moe"),
                moe_dropless=cfg.moe_dropless_prefill)
            return x, kv
        L = cfg.n_layers - (1 if fam == "moe" else 0)
        x, kvs = lm._stack_scan(cfg, params["blocks"], body, x,
                                jnp.arange(L, dtype=jnp.int32))
        if fam == "moe":
            kvs = _prepend_layer(kv0, kvs)
        cache.update(_attn_cache(cfg, kvs))

    elif fam == "rwkv":
        def body(lp, x, _):
            st = {"wkv": jnp.zeros((B, cfg.n_heads, cfg.d_head, cfg.d_head),
                                   jnp.float32),
                  "shift1": jnp.zeros((B, cfg.d_model), x.dtype),
                  "shift2": jnp.zeros((B, cfg.d_model), x.dtype)}
            x, st = lm.rwkv_block(cfg, lp, x, st)
            return x, st
        x, states = lm._stack_scan(cfg, params["blocks"], body, x)
        cache.update(states)

    elif fam == "hybrid":
        def fresh_state():
            return {"conv": jnp.zeros((B, cfg.conv_k - 1, cfg.inner),
                                      x.dtype),
                    "ssm": jnp.zeros((B, cfg.inner, cfg.ssm_state),
                                     jnp.float32)}

        if lm.hybrid_grouped(cfg):
            G, ge = cfg.n_layers // cfg.global_every, cfg.global_every
            grouped = jax.tree.map(
                lambda a: a.reshape((G, ge) + a.shape[1:]), params["blocks"])

            def group_body(gp, x, _):
                g0 = jax.tree.map(lambda a: a[0], gp)
                rest = jax.tree.map(lambda a: a[1:], gp)
                x, kv0, st0 = lm.hymba_block(cfg, g0, x, positions,
                                             fresh_state(), window=0)

                def inner(lp, x, __):
                    x, kv, st = lm.hymba_block(cfg, lp, x, positions,
                                               fresh_state(),
                                               window=cfg.window)
                    return x, (kv, st)
                x, (kvs, sts) = lm._stack_scan(cfg, rest, inner, x)
                # interleave group-local outputs back to layer order
                kv_all = jax.tree.map(
                    lambda a0, a: jnp.concatenate([a0[None], a], 0),
                    kv0, kvs)
                st_all = jax.tree.map(
                    lambda a0, a: jnp.concatenate([a0[None], a], 0),
                    st0, sts)
                return x, (kv_all, st_all)

            def outer(carry, gp):
                return lm._maybe_remat(cfg, group_body)(gp, carry, None)
            x, (kvs, states) = jax.lax.scan(outer, x, grouped)
            kvs = jax.tree.map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), kvs)
            states = jax.tree.map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), states)
        else:
            def body(lp, x, idx):
                x, kv, st = lm.hymba_block(cfg, lp, x, positions,
                                           fresh_state(),
                                           window=layer_window(cfg, idx))
                return x, (kv, st)
            x, (kvs, states) = lm._stack_scan(
                cfg, params["blocks"], body, x,
                jnp.arange(cfg.n_layers, dtype=jnp.int32))
        cache["k"], cache["v"] = kvs
        cache.update(states)

    elif fam == "encdec":
        # one encoder pass + per-layer cross-K/V via encode_cross — the
        # same function the chunked-prefill fold consumes, so the one-shot
        # and folded admission paths cannot drift apart
        xk, xv = encode_cross(cfg, params, batch["enc_embed"])

        def dec_body(lp, x, inp):
            kx, vx = inp
            x, kv = lm.cross_block(cfg, lp, x, positions,
                                   (kx.astype(x.dtype), vx.astype(x.dtype)))
            return x, kv
        x, kvs = lm._stack_scan(cfg, params["dec_blocks"], dec_body, x,
                                (xk, xv))
        cache["k"], cache["v"] = kvs
        cache["xk"], cache["xv"] = xk, xv

    elif fam == "vlm":
        vis = batch["vision_embed"].astype(x.dtype)
        k_ = cfg.cross_every
        G = cfg.n_layers // k_
        self_pp = jax.tree.map(
            lambda a: a.reshape((G, k_ - 1) + a.shape[1:]), params["blocks"])

        def group_body(gp, x, _):
            self_p, cross_p = gp

            def inner(lp, x, __):
                x, kv, _ = lm.decoder_block(cfg, lp, x, positions)
                return x, kv
            x, kvs = lm._stack_scan(cfg, self_p, inner, x)
            kx = _proj(vis, cross_p["xattn"]["wk"]).reshape(
                B, -1, cfg.n_kv_heads, cfg.d_head)
            vx = _proj(vis, cross_p["xattn"]["wv"]).reshape(
                B, -1, cfg.n_kv_heads, cfg.d_head)
            x, kv_self = lm.cross_block(cfg, cross_p, x, positions, (kx, vx))
            return x, (kvs, kv_self, (kx, vx))

        def outer(carry, inp):
            return _maybe_remat(cfg, group_body)(inp, carry, None)
        x, (kvs, kv_self, xkvs) = jax.lax.scan(
            outer, x, (self_pp, params["cross_blocks"]))
        cache["k"], cache["v"] = kvs
        cache["kx_self"], cache["vx_self"] = kv_self
        cache["xk"], cache["xv"] = xkvs
    else:
        raise ValueError(fam)

    if cfg.kv_quant and fam in ("decoder", "moe", "hybrid"):
        cache["k"], cache["k_scale"] = kvquant.quantize(cache["k"])
        cache["v"], cache["v_scale"] = kvquant.quantize(cache["v"])

    x = _norm_apply(cfg, params["final_norm"], x[:, -1:])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return cache, logits[:, 0]


def _prepend_layer(first, rest):
    """Per-layer cache arrays of a dense first layer (moe's ``dense0``)
    put before the scanned layers' stack."""
    return jax.tree.map(lambda a0, a: jnp.concatenate([a0[None], a], 0),
                        first, rest)


def _attn_cache(cfg: LMConfig, kvs) -> dict:
    """The attention layers' stacked outputs as cache keys: (k, v), or the
    latent rows under latent attention."""
    if cfg.mla:
        return {"latent": kvs}
    return {"k": kvs[0], "v": kvs[1]}


# ==========================================================================
# Chunked (suffix-only) prefill: resume from an existing KV prefix.
# ==========================================================================

def encode_cross(cfg: LMConfig, params, enc_embed):
    """Encoder pass + per-decoder-layer cross K/V projections (encdec).

    Returns (xk, xv): (L, B, enc_len, Hkv, Dh).  Factored out of
    :func:`prefill` so a chunked prefill fold runs the encoder exactly once
    per request — every chunk (and every resumed fold) then consumes the
    same arrays, keeping the fold's cross-attention bit-stable.
    """
    assert cfg.family == "encdec", cfg.family
    B = enc_embed.shape[0]
    enc = enc_embed.astype(cfg.dtype)
    enc = enc + _sinusoidal(enc.shape[1], cfg.d_model).astype(enc.dtype)
    enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1]), (B, enc.shape[1]))

    def enc_body(lp, h, _):
        h, _, _ = lm.decoder_block(cfg, lp, h, enc_pos, causal=False)
        return h, jnp.float32(0.0)
    enc, _ = lm._stack_scan(cfg, params["enc_blocks"], enc_body, enc)
    enc = _norm_apply(cfg, params["enc_norm"], enc)

    def proj_body(lp, h, _):
        kx = _proj(enc, lp["xattn"]["wk"]).reshape(
            B, -1, cfg.n_kv_heads, cfg.d_head)
        vx = _proj(enc, lp["xattn"]["wv"], lp["xattn"].get("bv")).reshape(
            B, -1, cfg.n_kv_heads, cfg.d_head)
        return h, (kx, vx)
    _, (xk, xv) = lm._stack_scan(cfg, params["dec_blocks"], proj_body,
                                 jnp.float32(0.0))
    return xk, xv


def prefill_chunked(cfg: LMConfig, params, batch, cache, q_offset: int):
    """Process one prompt chunk against an existing KV prefix.

    batch: {"tokens": (B, S_chunk)} — ONLY the tokens past the prefix.
    ``cache``: the prefix context — k/v of exactly ``q_offset`` positions
    on the sequence axis (zero-length arrays for a from-scratch fold), the
    conv taps / SSM state at the boundary for the hybrid family, and the
    precomputed :func:`encode_cross` xk/xv for encdec.  Returns (cache
    covering prefix+chunk, last-chunk-token logits).

    This is the step function of the serving **prefill fold**: a prompt is
    prefilled as a sequence of fixed-size chunks, and a radix prefix hit of
    H blocks resumes the fold at chunk H with the prefix gathered from the
    block arena.  Bit-exactness of the resume is structural: chunk j has
    the same static shapes whether the fold started at 0 or at H <= j, so
    XLA compiles the identical executable and the resumed fold reproduces
    the cold fold's K/V and logits bit-for-bit (tests/test_chunked_prefill
    asserts exactly this).  ``cfg.kv_quant`` is unsupported (the int8 cache
    no longer holds the pre-quantization values prefill attends over).
    """
    assert not cfg.kv_quant, "chunked prefill: int8 KV prefix unsupported"
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = lm.embed_tokens(cfg, params, tokens, pos_offset=q_offset)
    positions = jnp.broadcast_to(jnp.arange(q_offset, q_offset + S), (B, S))
    fam = cfg.family
    new_cache = {"len": jnp.int32(q_offset + S)}
    assert fam in ("decoder", "moe", "hybrid", "encdec"), fam
    if cfg.mla:
        prefix = cache["latent"]                # (L, B, q_offset, W)
        assert prefix.shape[-2] == q_offset, (prefix.shape, q_offset)
    else:
        pk_all, pv_all = cache["k"], cache["v"]  # (L, B, q_offset, Hkv, Dh)
        assert pk_all.shape[-3] == q_offset, (pk_all.shape, q_offset)
        prefix = (pk_all, pv_all)

    if fam in ("decoder", "moe"):
        if fam == "moe":
            p0 = jax.tree.map(lambda a: a[0], params["dense0"])
            x, kv0, _ = lm.decoder_block(
                cfg, p0, x, positions, q_offset=q_offset,
                kv_prefix=jax.tree.map(lambda a: a[0], prefix))

        def body(lp, x, inp):
            pre, idx = inp
            x, kv, _ = lm.decoder_block(cfg, lp, x, positions,
                                        window=layer_window(cfg, idx),
                                        moe_layer=(fam == "moe"),
                                        moe_dropless=cfg.moe_dropless_prefill,
                                        q_offset=q_offset,
                                        kv_prefix=pre)
            return x, kv
        L = cfg.n_layers - (1 if fam == "moe" else 0)
        off = 1 if fam == "moe" else 0
        x, kvs = lm._stack_scan(cfg, params["blocks"], body, x,
                                (jax.tree.map(lambda a: a[off:], prefix),
                                 jnp.arange(L, dtype=jnp.int32)))
        if fam == "moe":
            kvs = _prepend_layer(kv0, kvs)
        new_cache.update(_attn_cache(cfg, kvs))

    elif fam == "hybrid":
        if lm.hybrid_grouped(cfg):
            G, ge = cfg.n_layers // cfg.global_every, cfg.global_every
            regroup = lambda a: a.reshape((G, ge) + a.shape[1:])
            grouped = jax.tree.map(regroup, params["blocks"])
            xs = (grouped, regroup(pk_all), regroup(pv_all),
                  regroup(cache["conv"]), regroup(cache["ssm"]))

            def group_body(inp, x, _):
                gp, pk, pv, conv, ssm_st = inp
                g0 = jax.tree.map(lambda a: a[0], gp)
                rest = jax.tree.map(lambda a: a[1:], gp)
                x, kv0, st0 = lm.hymba_block(
                    cfg, g0, x, positions,
                    {"conv": conv[0], "ssm": ssm_st[0]}, window=0,
                    q_offset=q_offset, kv_prefix=(pk[0], pv[0]))

                def inner(lp, x, einp):
                    ipk, ipv, iconv, issm = einp
                    x, kv, st = lm.hymba_block(
                        cfg, lp, x, positions,
                        {"conv": iconv, "ssm": issm}, window=cfg.window,
                        q_offset=q_offset, kv_prefix=(ipk, ipv))
                    return x, (kv, st)
                x, (kvs, sts) = lm._stack_scan(
                    cfg, rest, inner, x,
                    (pk[1:], pv[1:], conv[1:], ssm_st[1:]))
                kv_all = jax.tree.map(
                    lambda a0, a: jnp.concatenate([a0[None], a], 0),
                    kv0, kvs)
                st_all = jax.tree.map(
                    lambda a0, a: jnp.concatenate([a0[None], a], 0),
                    st0, sts)
                return x, (kv_all, st_all)

            def outer(carry, inp):
                return lm._maybe_remat(cfg, group_body)(inp, carry, None)
            x, (kvs, states) = jax.lax.scan(outer, x, xs)
            kvs = jax.tree.map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), kvs)
            states = jax.tree.map(
                lambda a: a.reshape((cfg.n_layers,) + a.shape[2:]), states)
        else:
            def body(lp, x, inp):
                pk, pv, conv, ssm_st, idx = inp
                x, kv, st = lm.hymba_block(
                    cfg, lp, x, positions, {"conv": conv, "ssm": ssm_st},
                    window=layer_window(cfg, idx), q_offset=q_offset,
                    kv_prefix=(pk, pv))
                return x, (kv, st)
            x, (kvs, states) = lm._stack_scan(
                cfg, params["blocks"], body, x,
                (pk_all, pv_all, cache["conv"], cache["ssm"],
                 jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        new_cache["k"], new_cache["v"] = kvs
        new_cache.update(states)

    elif fam == "encdec":
        # cross K/V come precomputed from encode_cross — the whole fold
        # (every chunk, cold or resumed) consumes the same arrays
        def dec_body(lp, x, inp):
            pk, pv, kx, vx = inp
            x, kv = lm.cross_block(cfg, lp, x, positions,
                                   (kx.astype(x.dtype), vx.astype(x.dtype)),
                                   q_offset=q_offset, kv_prefix=(pk, pv))
            return x, kv
        x, kvs = lm._stack_scan(cfg, params["dec_blocks"], dec_body, x,
                                (pk_all, pv_all, cache["xk"], cache["xv"]))
        new_cache["k"], new_cache["v"] = kvs
        new_cache["xk"], new_cache["xv"] = cache["xk"], cache["xv"]

    x = _norm_apply(cfg, params["final_norm"], x[:, -1:])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return new_cache, logits[:, 0]


# ==========================================================================
# Decode (one token).
# ==========================================================================

def _decode_attn(cfg, p, x1, cache_k, cache_v, pos, *, window=0,
                 scales=None):
    """x1: (B,1,d).  Updates cache at ``pos`` and attends.

    ``scales``: (k_scale, v_scale) when the cache is int8-quantized
    (cfg.kv_quant) — writes quantize, reads dequantize (fused into the
    attention einsum's input).  Returns (out, new_k, new_v, new_scales).
    """
    B = x1.shape[0]
    q = _proj(x1, p["wq"], p.get("bq")).reshape(B, 1, cfg.n_heads, cfg.d_head)
    k1 = _proj(x1, p["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.d_head)
    v1 = _proj(x1, p["wv"], p.get("bv")).reshape(B, 1, cfg.n_kv_heads,
                                                 cfg.d_head)
    if cfg.pos_embedding == "rope":
        posb = jnp.broadcast_to(pos[None], (B, 1))
        q = rope.apply_rope(q, posb, cfg.rope_theta)
        k1 = rope.apply_rope(k1, posb, cfg.rope_theta)
    if scales is not None:
        ks, vs = scales
        k1q, k1s = kvquant.quantize(k1)
        v1q, v1s = kvquant.quantize(v1)
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k1q, pos, 1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v1q, pos, 1)
        ks = jax.lax.dynamic_update_slice_in_dim(ks, k1s, pos, 1)
        vs = jax.lax.dynamic_update_slice_in_dim(vs, v1s, pos, 1)
        k_full = kvquant.dequantize(cache_k, ks, cfg.dtype)
        v_full = kvquant.dequantize(cache_v, vs, cfg.dtype)
        o = attention.attend_decode(q, k_full, v_full, pos + 1,
                                    window=window)
        out = _proj(o.reshape(B, 1, cfg.n_heads * cfg.d_head), p["wo"],
                    p.get("bo"))
        return out, cache_k, cache_v, (ks, vs)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k1, pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v1, pos, axis=1)
    o = attention.attend_decode(q, cache_k, cache_v, pos + 1, window=window)
    out = _proj(o.reshape(B, 1, cfg.n_heads * cfg.d_head), p["wo"],
                p.get("bo"))
    return out, cache_k, cache_v, None


def _decode_attn_mla(cfg, p, x1, cache_lat, pos):
    """Latent attention's :func:`_decode_attn`: x1 (B,1,d); writes the new
    latent row at ``pos`` and attends in the absorbed form.  Returns
    (out, new cache_lat)."""
    B = x1.shape[0]
    q, row = lm.mla_project(cfg, p, x1, jnp.broadcast_to(pos[None], (B, 1)))
    cache_lat = jax.lax.dynamic_update_slice_in_dim(cache_lat, row, pos,
                                                    axis=1)
    o = attention.attend_latent(lm.mla_absorb(cfg, p, q[:, 0]), cache_lat,
                                jnp.broadcast_to(pos + 1, (B,)),
                                scale=lm.mla_scale(cfg), dv=cfg.kv_lora_rank)
    return lm.mla_unabsorb(cfg, p, o), cache_lat


def decode_step(cfg: LMConfig, params, cache, tokens):
    """tokens: (B, 1).  Returns (new_cache, logits (B, vocab_padded))."""
    B = tokens.shape[0]
    pos = cache["len"]
    x = params["embed"][tokens]
    if cfg.pos_embedding == "sinusoidal":
        i = jnp.arange(0, cfg.d_model, 2, dtype=jnp.float32)
        ang = pos.astype(jnp.float32) / jnp.power(10000.0, i / cfg.d_model)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None, :]
        x = x + pe.astype(x.dtype)
    fam = cfg.family
    new_cache = dict(cache)
    new_cache["len"] = pos + 1

    if fam in ("decoder", "moe"):
        blocks = params["blocks"]
        L = cfg.n_layers - (1 if fam == "moe" else 0)
        quant = cfg.kv_quant
        keys = ("latent",) if cfg.mla else \
            ("k", "v") + (("k_scale", "v_scale") if quant else ())

        def attend(lp, x, caches, window=0):
            """Returns (out, this layer's updated caches, in ``keys``
            order)."""
            z = _norm_apply(cfg, lp["ln1"], x)
            if cfg.mla:
                h, lat = _decode_attn_mla(cfg, lp["attn"], z, caches[0], pos)
                return h, (lat,)
            h, ck, cv, sc = _decode_attn(
                cfg, lp["attn"], z, caches[0], caches[1], pos, window=window,
                scales=tuple(caches[2:]) if quant else None)
            return h, (ck, cv) + (sc if quant else ())

        if fam == "moe":
            p0 = jax.tree.map(lambda a: a[0], params["dense0"])
            h, outs0 = attend(p0, x, [cache[key][0] for key in keys])
            x = x + h
            x = x + _mlp_apply(cfg, p0["mlp"], _norm_apply(cfg, p0["ln2"], x))

        def body(x, inp):
            lp, caches, idx = inp
            h, outc = attend(lp, x, caches, window=layer_window(cfg, idx))
            x = x + h
            z = _norm_apply(cfg, lp["ln2"], x)
            if fam == "moe":
                y, _ = lm.moe_ffn_decode(cfg, lp["moe"], z)
            else:
                y = _mlp_apply(cfg, lp["mlp"], z)
            return x + y, outc

        off = slice(1, None) if fam == "moe" else slice(None)
        x, outs = jax.lax.scan(
            body, x, (blocks, tuple(cache[key][off] for key in keys),
                      jnp.arange(L, dtype=jnp.int32)))
        if fam == "moe":
            outs = _prepend_layer(outs0, outs)
        new_cache.update(zip(keys, outs))

    elif fam == "rwkv":
        def body(x, inp):
            lp, wkv_st, sh1, sh2 = inp
            st = {"wkv": wkv_st, "shift1": sh1, "shift2": sh2}
            x, st = lm.rwkv_block(cfg, lp, x, st)
            return x, (st["wkv"], st["shift1"], st["shift2"])
        x, (wkv, s1, s2) = jax.lax.scan(
            body, x, (params["blocks"], cache["wkv"], cache["shift1"],
                      cache["shift2"]))
        new_cache.update(wkv=wkv, shift1=s1, shift2=s2)

    elif fam == "hybrid":
        quant = cfg.kv_quant

        def body(x, inp):
            if quant:
                lp, ck, cv, ks_, vs_, conv_st, ssm_st, idx = inp
                sc_in = (ks_, vs_)
            else:
                lp, ck, cv, conv_st, ssm_st, idx = inp
                sc_in = None
            z = _norm_apply(cfg, lp["ln1"], x)
            att, ck, cv, sc = _decode_attn(cfg, lp["attn"], z, ck, cv, pos,
                                           window=layer_window(cfg, idx),
                                           scales=sc_in)
            xz = _proj(z, lp["in_proj"])
            xm, gate = jnp.split(xz, 2, axis=-1)
            xm, conv_st = _causal_conv(xm, lp["conv_w"], conv_st)
            xm = jax.nn.silu(xm.astype(jnp.float32)).astype(x.dtype)
            dtr = lp["dt_proj"].shape[0]
            dbc = _proj(xm, lp["x_proj"])
            dt = jax.nn.softplus(
                _proj(dbc[..., :dtr], lp["dt_proj"]).astype(jnp.float32)
                + lp["dt_bias"].astype(jnp.float32))
            N = cfg.ssm_state
            y1, ssm_st = ssm.selective_step(
                xm[:, 0], dt[:, 0].astype(x.dtype), lp["A_log"],
                dbc[:, 0, dtr:dtr + N], dbc[:, 0, dtr + N:], lp["D_skip"],
                ssm_st)
            y = (y1[:, None] * jax.nn.silu(gate.astype(jnp.float32)
                                           ).astype(x.dtype))
            y = _proj(y, lp["ssm_out"])
            beta = lp["beta"].astype(jnp.float32)
            mixed = (beta[0] * _norm_apply(cfg, lp["norm_attn"], att
                                           ).astype(jnp.float32)
                     + beta[1] * _norm_apply(cfg, lp["norm_ssm"], y
                                             ).astype(jnp.float32)) * 0.5
            x = x + mixed.astype(x.dtype)
            x = x + _mlp_apply(cfg, lp["mlp"], _norm_apply(cfg, lp["ln2"], x))
            outc = (ck, cv) + (sc if quant else ()) + (conv_st, ssm_st)
            return x, outc

        xs_in = (params["blocks"], cache["k"], cache["v"])
        if quant:
            xs_in += (cache["k_scale"], cache["v_scale"])
        xs_in += (cache["conv"], cache["ssm"],
                  jnp.arange(cfg.n_layers, dtype=jnp.int32))
        x, outs = jax.lax.scan(body, x, xs_in)
        if quant:
            ks, vs, kss, vss, conv, ssm_s = outs
            new_cache.update(k=ks, v=vs, k_scale=kss, v_scale=vss,
                             conv=conv, ssm=ssm_s)
        else:
            ks, vs, conv, ssm_s = outs
            new_cache.update(k=ks, v=vs, conv=conv, ssm=ssm_s)

    elif fam == "encdec":
        def body(x, inp):
            lp, ck, cv, xk, xv = inp
            h, ck, cv, _ = _decode_attn(cfg, lp["attn"],
                                        _norm_apply(cfg, lp["ln1"], x),
                                        ck, cv, pos)
            x = x + h
            q = _proj(_norm_apply(cfg, lp["ln_x"], x), lp["xattn"]["wq"],
                      lp["xattn"].get("bq")).reshape(
                x.shape[0], 1, cfg.n_heads, cfg.d_head)
            o = attention.attend_decode(q, xk, xv, xk.shape[1])
            hx = _proj(o.reshape(x.shape[0], 1, -1), lp["xattn"]["wo"],
                       lp["xattn"].get("bo"))
            gate = jnp.tanh(lp["gate_attn"].astype(jnp.float32)).astype(x.dtype)
            x = x + gate * hx
            x = x + _mlp_apply(cfg, lp["mlp"], _norm_apply(cfg, lp["ln2"], x))
            return x, (ck, cv)
        x, (ks, vs) = jax.lax.scan(
            body, x, (params["dec_blocks"], cache["k"], cache["v"],
                      cache["xk"], cache["xv"]))
        new_cache.update(k=ks, v=vs)

    elif fam == "vlm":
        k_ = cfg.cross_every
        G = cfg.n_layers // k_
        self_pp = jax.tree.map(
            lambda a: a.reshape((G, k_ - 1) + a.shape[1:]), params["blocks"])

        def group(x, inp):
            self_p, cross_p, ck, cv, ckx, cvx, xk, xv = inp

            def inner(x, sinp):
                lp, ck_i, cv_i = sinp
                h, ck_i, cv_i, _ = _decode_attn(
                    cfg, lp["attn"], _norm_apply(cfg, lp["ln1"], x),
                    ck_i, cv_i, pos)
                x = x + h
                x = x + _mlp_apply(cfg, lp["mlp"],
                                   _norm_apply(cfg, lp["ln2"], x))
                return x, (ck_i, cv_i)
            x, (ck, cv) = jax.lax.scan(inner, x, (self_p, ck, cv))
            h, ckx, cvx, _ = _decode_attn(cfg, cross_p["attn"],
                                          _norm_apply(cfg, cross_p["ln1"], x),
                                          ckx, cvx, pos)
            x = x + h
            q = _proj(_norm_apply(cfg, cross_p["ln_x"], x),
                      cross_p["xattn"]["wq"]).reshape(
                x.shape[0], 1, cfg.n_heads, cfg.d_head)
            o = attention.attend_decode(q, xk, xv, xk.shape[1])
            hx = _proj(o.reshape(x.shape[0], 1, -1), cross_p["xattn"]["wo"])
            gate = jnp.tanh(cross_p["gate_attn"].astype(jnp.float32)
                            ).astype(x.dtype)
            x = x + gate * hx
            x = x + _mlp_apply(cfg, cross_p["mlp"],
                               _norm_apply(cfg, cross_p["ln2"], x))
            return x, (ck, cv, ckx, cvx)

        x, (ks, vs, kxs, vxs) = jax.lax.scan(
            group, x, (self_pp, params["cross_blocks"], cache["k"],
                       cache["v"], cache["kx_self"], cache["vx_self"],
                       cache["xk"], cache["xv"]))
        new_cache.update(k=ks, v=vs, kx_self=kxs, vx_self=vxs)
    else:
        raise ValueError(fam)

    x = _norm_apply(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return new_cache, logits[:, 0]


# ==========================================================================
# Decode (one token), in place against the paged block arena.
# ==========================================================================

def decode_moe_work(cfg: LMConfig, lanes: int, active: int) -> dict:
    """MoE work of one :func:`decode_step_paged` tick of ``lanes`` lanes,
    counted from shapes: ``moe_rows``, the rows the expert FFN computes
    (every lane, active or not, is a dispatch group of one token, in every
    MoE layer), and ``moe_picks``, the router picks the ``active`` lanes
    need."""
    layers = cfg.n_layers - 1               # layer 0 is dense
    return {"moe_rows": lanes * layers * lm.moe_ffn_decode_rows(cfg, 1),
            "moe_picks": active * cfg.top_k * layers}


def decode_attn_work(cfg: LMConfig, lens, bs: int, nb: int,
                     tiles: int | None = None) -> dict:
    """Dense paged attention work of one :func:`decode_step_paged` tick
    under ``backend="pallas"``, counted from shapes and summed over layers:
    ``attn_blocks_read``, the table entries
    ``kernels.paged_attn.paged_decode_attention`` computes (whole grid
    steps of ``tiles`` entries that hold a live one), and
    ``attn_blocks_live``, the blocks holding a position a lane attends.
    ``lens``: the tick's (S,) cache lengths, every lane as the kernel sees
    it (a lane attends ``lens + 1`` rows, inside each layer's window);
    ``nb``: table entries a lane; ``tiles`` defaults to the kernel's."""
    from repro.kernels.paged_attn import NO_WINDOW, live_entries, paged_tiles
    if tiles is None:
        tiles = paged_tiles(nb, bs, cfg.n_kv_heads, cfg.d_head,
                            jnp.dtype(cfg.dtype).itemsize)
    n = np.asarray(lens, np.int64) + 1
    windows = np.full(cfg.n_layers, NO_WINDOW)
    if cfg.window:
        glob = np.asarray(cfg.is_global_layer(np.arange(cfg.n_layers)))
        windows[~glob] = cfg.window
    read = live = 0
    for w, layers in zip(*np.unique(windows, return_counts=True)):
        lo, last = live_entries(n, w, bs)
        live += int(layers * np.sum(last - lo + 1))
        read += int(layers * tiles * np.sum(last // tiles - lo // tiles + 1))
    return {"attn_blocks_read": read, "attn_blocks_live": live}


def decode_step_paged(cfg: LMConfig, params, cache, tokens, *, tables, lens,
                      arena, wbids=None, kernel=None, interpret=None,
                      backend=None, cascade=None):
    """One batched decode tick reading K/V **in place** from the block arena.

    The gather-free counterpart of ``vmap(decode_step)`` over slot lanes:
    instead of materializing every lane's chain as a dense ``max_len``
    cache, each attention layer reads its K/V through the lane's block
    table (``lm.attn_decode_paged`` → ``attend_decode_paged`` in XLA, or
    the ``kernels/paged_attn.py`` scalar-prefetch kernel with
    ``kernel=True``), and the only persistent sequence-axis write is the
    new token's single row per layer, scattered once after the layer scan.

    cache   slot-stacked non-sequence state, exactly the paged adapter's
            dense dict: "len" (S,) plus hybrid conv/ssm and encdec/vlm
            xk/xv (leading axis = slot lanes).
    tokens  (S, 1) int32.
    tables  (S, nb) int32 arena block ids (trash-padded past each chain).
    lens    (S,) int32 per-lane lengths (== cache["len"]; the new token
            lands at position ``lens``).
    arena   :func:`init_paged_arena` dict (layer-leading block axis).
    wbids   (S,) int32 arena block each lane's new row lands in — the
            caller routes lanes that must not write (inactive, at capacity,
            pre-copy-on-write) to the trash block.  ``None`` derives the
            block from the table, routing out-of-range lanes to block 0
            (the pool's reserved trash block).

    Returns (new_arena, new_cache, logits (S, vocab_padded)).  With
    ``kernel=False`` the logits are bitwise-identical to the gather tick /
    dense-adapter oracle (pinned per family in tests/test_paged_decode.py):
    every position a lane can read holds the same bits in both layouts and
    everything else is masked to NEG_INF before the softmax.

    Maintenance note: the per-family layer bodies below deliberately
    mirror :func:`decode_step` (only the cache plumbing differs — scan xs
    are arena slices instead of per-layer dense caches, or none where the
    Pallas kernel reads the whole arena at the layer index, and the write
    is a row instead of a buffer).  Any numeric change to a family's decode
    body must land in BOTH functions; the bitwise parity suite exists to
    catch exactly that drift, so a paged-parity failure after touching
    :func:`decode_step` means this copy is stale, not that paging broke.
    """
    fam = cfg.family
    assert fam in ("decoder", "moe", "hybrid", "encdec", "vlm"), \
        f"in-place paged decode: unsupported family {fam}"
    # backend= is the per-layer read-path enum ("xla" | "pallas" |
    # "cascade", see repro.serve.backend); kernel= survives as its
    # deprecated boolean alias (True -> "pallas")
    if backend is None:
        backend = "pallas" if kernel else "xla"
    assert backend in ("xla", "pallas", "cascade"), \
        f"in-place paged decode: unknown backend {backend!r}"
    assert backend != "cascade" or cascade is not None, \
        "backend=\"cascade\" needs the group metadata in cascade="
    # encdec/vlm cache full-dtype (init_cache ignores kv_quant there)
    quant = cfg.kv_quant and fam not in ("encdec", "vlm")
    assert not (quant and backend != "xla"), \
        "in-place paged decode: only the XLA reference covers kv_quant"
    assert not (fam == "vlm" and backend != "xla"), \
        "in-place paged decode: only the XLA reference covers the vlm " \
        "grouped layout"
    S = tokens.shape[0]
    bs = arena["latent"].shape[-2] if cfg.mla else arena["k"].shape[-3]
    nb = tables.shape[1]
    pos = jnp.asarray(lens, jnp.int32)
    offs = pos % bs
    if wbids is None:
        blk = jnp.take_along_axis(tables, jnp.minimum(pos // bs, nb - 1)
                                  [:, None], axis=1)[:, 0]
        wbids = jnp.where(pos >= nb * bs, 0, blk)    # 0 = trash block
    x = params["embed"][tokens]                       # (S, 1, d)
    if cfg.pos_embedding == "sinusoidal":
        i = jnp.arange(0, cfg.d_model, 2, dtype=jnp.float32)
        ang = pos[:, None].astype(jnp.float32) / \
            jnp.power(10000.0, i / cfg.d_model)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)[:, None, :]
        x = x + pe.astype(x.dtype)
    new_cache = dict(cache)
    new_cache["len"] = pos + 1

    # the Pallas kernel reads the whole layer-leading arena at a layer
    # index, so the layer scan hands it no slice: a per-layer slice of the
    # arena is materialized whole, every block live or not, once per layer
    # and tick.  The XLA reads take per-layer slices as scan inputs.
    whole = backend == "pallas"

    def attn(lp, z, kv, layer, window=0):
        """``kv``: this layer's arena slices (k, v[, k_scale, v_scale]),
        empty when the kernel reads the whole arena at ``layer``.  Returns
        (out, *rows): rows are the sequence-axis writes this layer owes
        the arena — (k1, v1) plain, + (k1_scale, v1_scale) under the int8
        kv_quant layout, or the one latent row under latent attention."""
        if cfg.mla:
            out = lm.attn_decode_paged_mla(
                cfg, lp, z, arena["latent"] if whole else kv[0], tables, pos,
                backend=backend, layer=layer if whole else None,
                interpret=interpret)
            return out[0], out[1:]
        if whole:
            kb, vb, sc = arena["k"], arena["v"], None
        else:
            kb, vb, sc, layer = kv[0], kv[1], (kv[2:] or None), None
        out = lm.attn_decode_paged(cfg, lp, z, kb, vb, tables, pos,
                                   window=window, backend=backend,
                                   cascade=cascade, interpret=interpret,
                                   scales=sc, layer=layer)
        return out[0], out[1:]

    def layer_arenas(sl):
        if whole:
            return ()
        if cfg.mla:
            return (arena["latent"][sl],)
        out = (arena["k"][sl], arena["v"][sl])
        if quant:
            out += (arena["k_scale"][sl], arena["v_scale"][sl])
        return out

    if fam in ("decoder", "moe"):
        first = 1 if fam == "moe" else 0    # moe's layer 0 is dense0
        L = cfg.n_layers - first

        def body(x, inp):
            lp, idx = inp[0], inp[-1]
            h, rows = attn(lp["attn"], _norm_apply(cfg, lp["ln1"], x),
                           inp[1:-1], idx + first,
                           window=layer_window(cfg, idx))
            x = x + h
            z = _norm_apply(cfg, lp["ln2"], x)
            if fam == "moe":
                # per-lane dispatch groups of one token, exactly the
                # vmapped dense tick's routing (a lane's output must not
                # depend on which other lanes share its decode batch);
                # decode_moe_work counts the rows this dispatch computes
                y = jax.vmap(lambda zi: lm.moe_ffn_decode(
                    cfg, lp["moe"], zi[None])[0][0])(z)
            else:
                y = _mlp_apply(cfg, lp["mlp"], z)
            return x + y, rows

        if fam == "moe":
            p0 = jax.tree.map(lambda a: a[0], params["dense0"])
            h, rows0 = attn(p0["attn"], _norm_apply(cfg, p0["ln1"], x),
                            layer_arenas(0), 0)
            x = x + h
            x = x + _mlp_apply(cfg, p0["mlp"], _norm_apply(cfg, p0["ln2"], x))
        x, rows = jax.lax.scan(
            body, x, (params["blocks"],) + layer_arenas(slice(first, None))
            + (jnp.arange(L, dtype=jnp.int32),))
        if fam == "moe":
            rows = tuple(jnp.concatenate([r0[None], r], 0)
                         for r0, r in zip(rows0, rows))

    elif fam == "hybrid":
        def body(x, inp):
            lp, conv_st, ssm_st, idx = inp[0], inp[-3], inp[-2], inp[-1]
            z = _norm_apply(cfg, lp["ln1"], x)
            att, kv_rows = attn(lp["attn"], z, inp[1:-3], idx,
                                window=layer_window(cfg, idx))
            xz = _proj(z, lp["in_proj"])
            xm, gate = jnp.split(xz, 2, axis=-1)
            xm, conv_st = _causal_conv(xm, lp["conv_w"], conv_st)
            xm = jax.nn.silu(xm.astype(jnp.float32)).astype(x.dtype)
            dtr = lp["dt_proj"].shape[0]
            dbc = _proj(xm, lp["x_proj"])
            dt = jax.nn.softplus(
                _proj(dbc[..., :dtr], lp["dt_proj"]).astype(jnp.float32)
                + lp["dt_bias"].astype(jnp.float32))
            N = cfg.ssm_state
            y1, ssm_st = ssm.selective_step(
                xm[:, 0], dt[:, 0].astype(x.dtype), lp["A_log"],
                dbc[:, 0, dtr:dtr + N], dbc[:, 0, dtr + N:], lp["D_skip"],
                ssm_st)
            y = (y1[:, None] * jax.nn.silu(gate.astype(jnp.float32)
                                           ).astype(x.dtype))
            y = _proj(y, lp["ssm_out"])
            beta = lp["beta"].astype(jnp.float32)
            mixed = (beta[0] * _norm_apply(cfg, lp["norm_attn"], att
                                           ).astype(jnp.float32)
                     + beta[1] * _norm_apply(cfg, lp["norm_ssm"], y
                                             ).astype(jnp.float32)) * 0.5
            x = x + mixed.astype(x.dtype)
            x = x + _mlp_apply(cfg, lp["mlp"], _norm_apply(cfg, lp["ln2"], x))
            return x, kv_rows + (conv_st, ssm_st)

        x, outs = jax.lax.scan(
            body, x, (params["blocks"],) + layer_arenas(slice(None))
            + (jnp.moveaxis(cache["conv"], 1, 0)[:, :, 0],
               jnp.moveaxis(cache["ssm"], 1, 0)[:, :, 0],
               jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        rows, (conv, ssm_s) = outs[:-2], outs[-2:]
        new_cache["conv"] = jnp.moveaxis(conv, 1, 0)[:, :, None]
        new_cache["ssm"] = jnp.moveaxis(ssm_s, 1, 0)[:, :, None]

    elif fam == "encdec":
        def body(x, inp):
            lp, xk, xv, idx = inp[0], inp[-3], inp[-2], inp[-1]
            h, rows = attn(lp["attn"], _norm_apply(cfg, lp["ln1"], x),
                           inp[1:-3], idx)
            x = x + h
            q = _proj(_norm_apply(cfg, lp["ln_x"], x), lp["xattn"]["wq"],
                      lp["xattn"].get("bq")).reshape(
                S, 1, cfg.n_heads, cfg.d_head)
            o = attention.attend_decode(q, xk, xv, xk.shape[1])
            hx = _proj(o.reshape(S, 1, -1), lp["xattn"]["wo"],
                       lp["xattn"].get("bo"))
            gate = jnp.tanh(lp["gate_attn"].astype(jnp.float32)
                            ).astype(x.dtype)
            x = x + gate * hx
            x = x + _mlp_apply(cfg, lp["mlp"], _norm_apply(cfg, lp["ln2"], x))
            return x, rows

        x, rows = jax.lax.scan(
            body, x, (params["dec_blocks"],) + layer_arenas(slice(None))
            + (jnp.moveaxis(cache["xk"], 1, 0)[:, :, 0],
               jnp.moveaxis(cache["xv"], 1, 0)[:, :, 0],
               jnp.arange(cfg.n_layers, dtype=jnp.int32)))

    elif fam == "vlm":
        k_ = cfg.cross_every
        G = cfg.n_layers // k_
        self_pp = jax.tree.map(
            lambda a: a.reshape((G, k_ - 1) + a.shape[1:]), params["blocks"])

        def group(x, inp):
            self_p, cross_p, kb_g, vb_g, kxb, vxb, xk, xv = inp

            def inner(x, sinp):
                lp, kb, vb = sinp
                h, rows_i = attn(lp["attn"], _norm_apply(cfg, lp["ln1"], x),
                                 (kb, vb), None)
                x = x + h
                x = x + _mlp_apply(cfg, lp["mlp"],
                                   _norm_apply(cfg, lp["ln2"], x))
                return x, rows_i
            x, self_rows = jax.lax.scan(inner, x, (self_p, kb_g, vb_g))
            h, x_rows = attn(cross_p["attn"],
                             _norm_apply(cfg, cross_p["ln1"], x), (kxb, vxb),
                             None)
            x = x + h
            q = _proj(_norm_apply(cfg, cross_p["ln_x"], x),
                      cross_p["xattn"]["wq"]).reshape(
                S, 1, cfg.n_heads, cfg.d_head)
            o = attention.attend_decode(q, xk, xv, xk.shape[1])
            hx = _proj(o.reshape(S, 1, -1), cross_p["xattn"]["wo"])
            gate = jnp.tanh(cross_p["gate_attn"].astype(jnp.float32)
                            ).astype(x.dtype)
            x = x + gate * hx
            x = x + _mlp_apply(cfg, cross_p["mlp"],
                               _norm_apply(cfg, cross_p["ln2"], x))
            return x, (self_rows, x_rows)

        x, (self_rows, x_rows) = jax.lax.scan(
            group, x, (self_pp, params["cross_blocks"], arena["k"],
                       arena["v"], arena["kx_self"], arena["vx_self"],
                       jnp.moveaxis(cache["xk"], 1, 0)[:, :, 0],
                       jnp.moveaxis(cache["xv"], 1, 0)[:, :, 0]))
        # grouped rows: self k/v (G, k-1, S, Hkv, Dh), cross-layer self
        # k/v (G, S, Hkv, Dh) — ranks the generalized write below absorbs
        rows = (self_rows[0], self_rows[1], x_rows[0], x_rows[1])

    # the tick's only sequence-axis write: one (S, Hkv, Dh) row per layer
    # (+ the f32 scale rows under kv_quant), landed at (block, offset) per
    # lane — trash-routed lanes are absorbed by the reserved block 0.  The
    # kernel leg scatters through kernels.paged_attn.scatter_kv_rows,
    # whose input_output_aliases update the arena buffers in place instead
    # of functionally rebuilding every layer slice (XLA donation already
    # covers the .at[].set reference leg).
    new_arena = dict(arena)
    if fam == "vlm":
        row_keys = ("k", "v", "kx_self", "vx_self")
    elif cfg.mla:
        row_keys = ("latent",)
    else:
        row_keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    if backend == "pallas" and cfg.mla:
        from repro.kernels.paged_attn import scatter_latent_rows
        new_arena["latent"] = scatter_latent_rows(
            arena["latent"], rows[0], wbids, offs, interpret=interpret)
    elif backend == "pallas":
        from repro.kernels.paged_attn import scatter_kv_rows
        new_arena["k"], new_arena["v"] = scatter_kv_rows(
            arena["k"], arena["v"], rows[0], rows[1], wbids, offs,
            interpret=interpret)
    else:
        for key, r in zip(row_keys, rows):
            # leading layer axes vary per key (one for decoder k/v, two
            # for vlm's grouped self k/v, one for its cross-layer self
            # k/v); the (block, B=1, offset) triple sits just before the
            # row's trailing axes — see arena_block_axis
            idx = (slice(None),) * arena_block_axis(arena[key], key) + \
                (wbids, 0, offs)
            new_arena[key] = arena[key].at[idx].set(r)

    x = _norm_apply(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return new_arena, new_cache, logits[:, 0]
