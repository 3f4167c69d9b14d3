"""Attention: GQA / MHA / cross, memory-efficient chunked ("flash-style in
XLA") for train/prefill and masked single-shot for decode.

The chunked path is a double ``lax.scan`` (query chunks x KV chunks) with an
online-softmax accumulator, so peak memory is O(q_chunk x kv_chunk) scores
per head instead of O(S^2); XLA keeps the HLO compact (one scan body), which
matters for the 126-layer dry-run compiles.  Scores/softmax accumulate in f32.

Sliding-window masks reuse the same body (mask-only; no dynamic skipping —
shapes stay static for SPMD).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.dist.sharding import hint

NEG_INF = -1e30


def _repeat_kv(k, n_rep: int):
    """(B, S, Hkv, D) -> (B, S, Hkv*n_rep, D) by broadcast (GQA)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d))
    return k.reshape(b, s, h * n_rep, d)


def _static_zero(window) -> bool:
    return isinstance(window, int) and window == 0


def _mask(q_pos, k_pos, causal: bool, window):
    """(Sq, Sk) bool validity mask from absolute positions.

    ``window`` may be a traced scalar (per-layer global/sliding selection
    inside a layer scan encodes "global" as a huge window); a static 0 means
    no windowing at all.
    """
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), jnp.bool_)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if not _static_zero(window):
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def attend_chunked(q, k, v, *, causal: bool = True, window: int = 0,
                   q_offset: int = 0, q_chunk: int = 512, kv_chunk: int = 1024,
                   ) -> jax.Array:
    """q: (B, Sq, Hq, D); k, v: (B, Sk, Hkv, D).  Returns (B, Sq, Hq, D).

    Flash-attention-style: online softmax forward, and a custom VJP that
    RECOMPUTES probabilities in the backward instead of letting autodiff
    store every (q_chunk x kv_chunk) probability tile as a scan residual —
    the O(S^2) f32 residual traffic was the dominant HBM term in the
    baseline dry-run (see EXPERIMENTS.md §Perf iteration 1).

    ``q_offset``: absolute position of q[0] relative to k[0] (chunked prefill
    against an existing cache uses q_offset > 0).  ``window`` may be traced
    (per-layer sliding/global selection); it participates as an array arg.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    n_rep = Hq // Hkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    nq, nk = -(-Sq // qc), -(-Sk // kc)
    # pad to chunk multiples (masked out via positions)
    q = jnp.pad(q, ((0, 0), (0, nq * qc - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * kc - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * kc - Sk), (0, 0), (0, 0)))

    q = hint(q, "batch", None, "model", None)
    k = hint(k, "batch", None, "model", None)
    v = hint(v, "batch", None, "model", None)
    q = q.reshape(B, nq, qc, Hq, D).transpose(1, 0, 3, 2, 4)   # (nq,B,H,qc,D)
    k = k.reshape(B, nk, kc, Hq, D).transpose(1, 0, 3, 2, 4)
    v = v.reshape(B, nk, kc, Hq, D).transpose(1, 0, 3, 2, 4)
    q = hint(q, None, "batch", "model", None, None)
    k = hint(k, None, "batch", "model", None, None)
    v = hint(v, None, "batch", "model", None, None)

    # window / offsets as f32 scalars so custom_vjp cotangents are trivial
    warr = jnp.asarray(window if not _static_zero(window) else (1 << 30),
                       jnp.float32)
    outs = _flash(q, k, v, warr, jnp.float32(q_offset), jnp.float32(Sk),
                  causal, qc, kc)
    outs = hint(outs, None, "batch", "model", None, None)
    outs = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * qc, Hq, D)
    return hint(outs[:, :Sq].astype(v.dtype), "batch", None, "model", None)


def _tile_mask(q_pos, k_pos, causal, window, sk):
    """q_pos/k_pos: f32 position vectors; window/sk: f32 scalars."""
    m = k_pos[None, :] < sk
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash(q, k, v, window, q_offset, sk, causal, qc, kc):
    out, _ = _flash_fwd_impl(q, k, v, window, q_offset, sk, causal, qc, kc)
    return out


def _flash_fwd_impl(q, k, v, window, q_offset, sk, causal, qc, kc):
    """q: (nq,B,H,qc,D); k,v: (nk,B,H,kc,D) -> out (nq,B,H,qc,D), lse."""
    nq, B, H, _, D = q.shape
    nk = k.shape[0]
    scale = D ** -0.5

    def q_body(_, q_i_and_idx):
        q_i, iq = q_i_and_idx
        q_pos = q_offset + (iq * qc + jnp.arange(qc)).astype(jnp.float32)

        def kv_body(carry, k_j_v_j_idx):
            m_prev, l_prev, acc = carry
            k_j, v_j, jk = k_j_v_j_idx
            k_pos = (jk * kc + jnp.arange(kc)).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(q_pos, k_pos, causal, window, sk)
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0), (k, v, jnp.arange(nk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out.astype(v.dtype), lse)

    _, (outs, lses) = jax.lax.scan(q_body, None, (q, jnp.arange(nq)))
    return outs, lses


def _flash_fwd(q, k, v, window, q_offset, sk, causal, qc, kc):
    out, lse = _flash_fwd_impl(q, k, v, window, q_offset, sk, causal, qc, kc)
    return out, (q, k, v, out, lse, window, q_offset, sk)


def _flash_bwd(causal, qc, kc, res, g):
    """FA2 backward: recompute p tiles from (q, k, lse); never store S^2."""
    q, k, v, out, lse, window, q_offset, sk = res
    nq, B, H, _, D = q.shape
    nk = k.shape[0]
    scale = D ** -0.5
    g = g.astype(jnp.float32)
    # delta_i = rowsum(dout * out)
    delta = jnp.sum(g * out.astype(jnp.float32), axis=-1)   # (nq,B,H,qc)

    def kv_body(dq_acc, kv_idx):
        k_j, v_j, jk = kv_idx
        k_pos = (jk * kc + jnp.arange(kc)).astype(jnp.float32)

        def q_body(carry, q_idx):
            dk_j, dv_j = carry
            q_i, g_i, lse_i, delta_i, iq = q_idx
            q_pos = q_offset + (iq * qc + jnp.arange(qc)).astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(q_pos, k_pos, causal, window, sk)
            s = jnp.where(mask[None, None], s, NEG_INF)
            p = jnp.exp(s - lse_i[..., None])                # (B,H,qc,kc)
            dv_j = dv_j + jnp.einsum("bhqk,bhqd->bhkd",
                                     p.astype(g_i.dtype), g_i,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", g_i,
                            v_j.astype(jnp.float32),
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta_i[..., None]) * scale       # (B,H,qc,kc)
            dsl = ds.astype(k_j.dtype)
            dq_i = jnp.einsum("bhqk,bhkd->bhqd", dsl, k_j,
                              preferred_element_type=jnp.float32)
            dk_j = dk_j + jnp.einsum("bhqk,bhqd->bhkd", dsl,
                                     q_i.astype(k_j.dtype),
                                     preferred_element_type=jnp.float32)
            return (dk_j, dv_j), dq_i

        zeros = jnp.zeros((B, H, kc, D), jnp.float32)
        (dk_j, dv_j), dq_inc = jax.lax.scan(
            q_body, (zeros, zeros),
            (q, g, lse, delta, jnp.arange(nq)))
        return dq_acc + dq_inc, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, H, q.shape[3], D), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(kv_body, dq0, (k, v, jnp.arange(nk)))
    zero = jnp.zeros((), jnp.float32)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero, zero, zero)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attend_sliding(q, k, v, *, window: int, q_offset: int = 0,
                   q_chunk: int = 512) -> jax.Array:
    """Sliding-window attention with true KV skipping (static ``window``).

    Each q chunk attends only to the ``window + q_chunk`` keys it can see —
    FLOPs and traffic are O(S·window) instead of O(S^2) (the §Perf
    iteration-2 fix for sliding-window layers; a 21x FLOP cut at 32k/1024).
    q: (B, S, Hq, D); k, v: (B, S, Hkv, D) — self-attention layout:
    queries and keys share an origin (``q_offset`` shifts both together),
    so a *resumed* prefill — queries starting mid-sequence against a longer
    prefix+suffix key axis — must go through :func:`attend_chunked`'s
    mask-only windowing instead (``lm._attn_apply`` routes this).
    """
    B, S, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    k = _repeat_kv(k, Hq // Hkv)
    v = _repeat_kv(v, Hq // Hkv)
    qc = min(q_chunk, S)
    nq = -(-S // qc)
    L = window + qc                      # static slice length per q chunk
    qp = jnp.pad(q, ((0, 0), (0, nq * qc - S), (0, 0), (0, 0)))
    # front-pad keys by `window` so slice starts are always in range
    kp = jnp.pad(k, ((0, 0), (window, nq * qc - Sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, nq * qc - Sk), (0, 0), (0, 0)))
    qp = hint(qp, "batch", None, "model", None)
    kp = hint(kp, "batch", None, "model", None)
    vp = hint(vp, "batch", None, "model", None)
    q5 = qp.reshape(B, nq, qc, Hq, D).transpose(1, 0, 3, 2, 4)  # (nq,B,H,qc,D)
    out = _sliding(q5, kp.transpose(0, 2, 1, 3), vp.transpose(0, 2, 1, 3),
                   jnp.float32(q_offset), jnp.float32(Sk), window, qc)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, nq * qc, Hq, D)
    return out[:, :S].astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _sliding(q5, kt, vt, q_offset, sk, window, qc):
    return _sliding_fwd_impl(q5, kt, vt, q_offset, sk, window, qc)[0]


def _sliding_tile(q_i, k_i, iq, q_offset, sk, window, qc):
    """One q chunk vs its (window+qc) key slice.  Returns (s, mask)."""
    D = q_i.shape[-1]
    L = window + qc
    q_pos = q_offset + (iq * qc + jnp.arange(qc)).astype(jnp.float32)
    k_pos = q_offset + (iq * qc - window + jnp.arange(L)).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", q_i, k_i,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    mask = (k_pos[None, :] >= q_offset) & (k_pos[None, :] < q_offset + sk)
    mask &= q_pos[:, None] >= k_pos[None, :]
    mask &= (q_pos[:, None] - k_pos[None, :]) < window
    return jnp.where(mask[None, None], s, NEG_INF)


def _sliding_fwd_impl(q5, kt, vt, q_offset, sk, window, qc):
    """q5: (nq,B,H,qc,D); kt, vt: (B,H,window+nq*qc,D)."""
    nq, B, H, _, D = q5.shape
    L = window + qc

    def body(_, q_idx):
        q_i, iq = q_idx
        k_i = jax.lax.dynamic_slice_in_dim(kt, iq * qc, L, axis=2)
        v_i = jax.lax.dynamic_slice_in_dim(vt, iq * qc, L, axis=2)
        s = _sliding_tile(q_i, k_i, iq, q_offset, sk, window, qc)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v_i.dtype), v_i,
                       preferred_element_type=jnp.float32)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return None, (o.astype(vt.dtype), m + jnp.log(jnp.maximum(l, 1e-30)))

    _, (outs, lses) = jax.lax.scan(body, None, (q5, jnp.arange(nq)))
    return outs, lses


def _sliding_fwd(q5, kt, vt, q_offset, sk, window, qc):
    outs, lses = _sliding_fwd_impl(q5, kt, vt, q_offset, sk, window, qc)
    return outs, (q5, kt, vt, outs, lses, q_offset, sk)


def _sliding_bwd(window, qc, res, g):
    q5, kt, vt, outs, lses, q_offset, sk = res
    nq, B, H, _, D = q5.shape
    L = window + qc
    g = g.astype(jnp.float32)
    delta = jnp.sum(g * outs.astype(jnp.float32), axis=-1)

    def body(carry, q_idx):
        dk_acc, dv_acc = carry
        q_i, g_i, lse_i, delta_i, iq = q_idx
        k_i = jax.lax.dynamic_slice_in_dim(kt, iq * qc, L, axis=2)
        v_i = jax.lax.dynamic_slice_in_dim(vt, iq * qc, L, axis=2)
        s = _sliding_tile(q_i, k_i, iq, q_offset, sk, window, qc)
        p = jnp.exp(s - lse_i[..., None])
        dv_i = jnp.einsum("bhqk,bhqd->bhkd", p.astype(g_i.dtype), g_i,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g_i, v_i.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta_i[..., None]) * (D ** -0.5)
        dsl = ds.astype(kt.dtype)
        dq_i = jnp.einsum("bhqk,bhkd->bhqd", dsl, k_i,
                          preferred_element_type=jnp.float32)
        dk_i = jnp.einsum("bhqk,bhqd->bhkd", dsl, q_i.astype(kt.dtype),
                          preferred_element_type=jnp.float32)
        upd = jax.lax.dynamic_slice_in_dim(dk_acc, iq * qc, L, axis=2) + dk_i
        dk_acc = jax.lax.dynamic_update_slice_in_dim(dk_acc, upd, iq * qc,
                                                     axis=2)
        updv = jax.lax.dynamic_slice_in_dim(dv_acc, iq * qc, L, axis=2) + dv_i
        dv_acc = jax.lax.dynamic_update_slice_in_dim(dv_acc, updv, iq * qc,
                                                     axis=2)
        return (dk_acc, dv_acc), dq_i

    zk = jnp.zeros(kt.shape, jnp.float32)
    (dk, dv), dq = jax.lax.scan(
        body, (zk, zk), (q5, g, lses, delta, jnp.arange(nq)))
    zero = jnp.zeros((), jnp.float32)
    return (dq.astype(q5.dtype), dk.astype(kt.dtype), dv.astype(vt.dtype),
            zero, zero)


_sliding.defvjp(_sliding_fwd, _sliding_bwd)


def attend_decode(q, k_cache, v_cache, cache_len, *, window=0) -> jax.Array:
    """One-token decode attention against a cache.

    q: (B, 1, Hq, D); k_cache, v_cache: (B, Smax, Hkv, D);
    cache_len: scalar int32 — number of valid cache positions (the new token's
    K/V must already be written at cache_len - 1).
    """
    B, _, Hq, D = q.shape
    _, Smax, Hkv, _ = k_cache.shape
    n_rep = Hq // Hkv
    scale = D ** -0.5
    qh = q[:, 0].reshape(B, Hkv, n_rep, D)
    qh = hint(qh, "batch", "model", None, None)
    s = jnp.einsum("bhrd,bshd->bhrs", qh, k_cache,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(Smax)
    valid = pos[None, None, None, :] < cache_len
    if not _static_zero(window):
        valid &= pos[None, None, None, :] >= (cache_len - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrs,bshd->bhrd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(v_cache.dtype)


def gather_paged_kv(arena, block_table) -> jax.Array:
    """arena: (num_blocks, bs, Hkv, D); block_table: (B, nb) int32.

    Returns the dense (B, nb*bs, Hkv, D) view of each row's block chain —
    the ``jnp.take``-based gather that feeds :func:`attend_decode`.  Unused
    table entries point at the trash block (id 0); whatever it holds is
    masked out by ``cache_len`` downstream.
    """
    nb, bs = block_table.shape[1], arena.shape[1]
    g = jnp.take(arena, block_table, axis=0)        # (B, nb, bs, Hkv, D)
    return g.reshape(g.shape[0], nb * bs, *g.shape[3:])


def attend_decode_paged(q, k_arena, v_arena, block_table, cache_len, *,
                        window=0, new_kv=None, scales=None,
                        out_dtype=None, backend=None, cascade=None,
                        layer=None, interpret=None) -> jax.Array:
    """One-token decode attention against a *paged* cache (single layer).

    q: (B, 1, Hq, D); k_arena, v_arena: (num_blocks, bs, Hkv, D);
    block_table: (B, nb) int32 block ids; cache_len: (B,) int32 per-row
    valid lengths (the new token's K/V already written at cache_len - 1).

    ``new_kv``: optional (k1, v1), each (B, Hkv, D) — the current token's
    K/V row, inserted into the gathered view at ``cache_len - 1`` instead
    of requiring the caller to have scattered it into the arena first.
    This is how the in-place decode tick reads the token it is mid-way
    through writing: the arena write happens once, after the layer scan
    (mode="drop" so a lane already at capacity never corrupts a live row;
    such lanes are masked upstream and their output is discarded).

    ``scales``: optional (k_scale_arena, v_scale_arena), each
    (num_blocks, bs, Hkv, 1) f32 — the int8 ``kv_quant`` layout.  The
    gathered view is dequantized to ``out_dtype`` *after* the per-table
    gather (elementwise, so it is bit-identical to dequantizing the dense
    cache and gathering), and ``new_kv`` must then carry the already
    dequantized current row — exactly what the dense quant tick attends
    over after writing the quantized row.

    ``backend`` is the per-layer read-path dispatch (see
    :mod:`repro.serve.backend`): ``None``/``"xla"`` gathers each row's
    block chain into the dense layout and applies the same masked softmax
    as :func:`attend_decode` with a per-row length vector; ``"pallas"``
    routes to :func:`repro.kernels.paged_attn.paged_decode_attention`
    (no gather — the block table rides in as a scalar-prefetch operand);
    ``"cascade"`` routes to :func:`attend_decode_cascade` with the group
    metadata in ``cascade``.  The ``"xla"`` body is the reference
    semantics the other two are pinned against.

    ``layer`` (``"pallas"`` only): the arenas are the whole layer-leading
    ``(L, num_blocks, 1, bs, Hkv, D)`` arrays and the kernel reads this
    layer of them (see ``kernels.paged_attn.paged_decode_attention``).
    """
    if backend == "pallas":
        from repro.kernels.paged_attn import paged_decode_attention
        assert scales is None, "pallas backend does not cover kv_quant"
        nk = None if new_kv is None else (new_kv[0], new_kv[1])
        out = paged_decode_attention(q[:, 0], k_arena, v_arena, block_table,
                                     cache_len, window=window, new_kv=nk,
                                     layer=layer, interpret=interpret)
        return out[:, None]
    assert layer is None, "only the pallas backend reads a whole arena"
    if backend == "cascade":
        assert cascade is not None, "cascade backend needs group metadata"
        return attend_decode_cascade(q, k_arena, v_arena, cascade, cache_len,
                                     window=window, new_kv=new_kv,
                                     scales=scales, out_dtype=out_dtype,
                                     interpret=interpret)
    assert backend in (None, "xla"), f"unknown attention backend {backend!r}"
    B, _, Hq, D = q.shape
    Hkv = k_arena.shape[2]
    n_rep = Hq // Hkv
    scale = D ** -0.5
    k = gather_paged_kv(k_arena, block_table)       # (B, S, Hkv, D)
    v = gather_paged_kv(v_arena, block_table)
    if scales is not None:
        from repro.serve import kvquant
        ks = gather_paged_kv(scales[0], block_table)
        vs = gather_paged_kv(scales[1], block_table)
        k = kvquant.dequantize(k, ks, out_dtype)
        v = kvquant.dequantize(v, vs, out_dtype)
    if new_kv is not None:
        k1, v1 = new_kv
        rows = jnp.arange(B)
        k = k.at[rows, cache_len - 1].set(k1.astype(k.dtype), mode="drop")
        v = v.at[rows, cache_len - 1].set(v1.astype(v.dtype), mode="drop")
    qh = q[:, 0].reshape(B, Hkv, n_rep, D)
    s = jnp.einsum("bhrd,bshd->bhrs", qh, k,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k.shape[1])
    valid = pos[None, None, None, :] < cache_len[:, None, None, None]
    if not _static_zero(window):
        valid &= pos[None, None, None, :] >= \
            (cache_len[:, None, None, None] - window)
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhrs,bshd->bhrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, Hq, D).astype(v.dtype)


def attend_latent(q, lat, cache_len, *, scale: float, dv: int) -> jax.Array:
    """One-token latent attention (MLA's absorbed decode form) against
    dense latent rows: a single key head whose first ``dv`` lanes are also
    the values.

    q: (B, Hq, W) absorbed queries; lat: (B, Smax, W) latent rows;
    cache_len: (B,) int32 valid rows per lane.  Returns (B, Hq, dv) in
    lat.dtype.
    """
    s = jnp.einsum("bhw,bsw->bhs", q, lat,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(lat.shape[1])
    s = jnp.where(pos[None, None, :] < cache_len[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", p.astype(lat.dtype), lat[..., :dv],
                     preferred_element_type=jnp.float32)
    return out.astype(lat.dtype)


def attend_decode_latent(q, lat_arena, block_table, cache_len, *, new_row,
                         scale: float, dv: int, backend=None, layer=None,
                         interpret=None) -> jax.Array:
    """One-token latent attention against a *paged* latent cache.

    q: (B, Hq, W) absorbed queries; lat_arena: (num_blocks, bs, W) under
    ``"xla"``, the whole (L, num_blocks, 1, bs, W) arena read at ``layer``
    under ``"pallas"``; block_table (B, nb); cache_len (B,) valid rows
    including the new one, whose row ``new_row`` (B, W) is overlaid at
    ``cache_len - 1``.
    ``"xla"`` gathers each chain into the dense layout and runs
    :func:`attend_latent` — the reference the ``"pallas"`` kernel
    (``kernels.paged_attn.latent_decode_attention``) is pinned against.
    Returns (B, Hq, dv).
    """
    if backend == "pallas":
        from repro.kernels.paged_attn import latent_decode_attention
        return latent_decode_attention(q, lat_arena, block_table, cache_len,
                                       new_row=new_row, scale=scale, dv=dv,
                                       layer=layer, interpret=interpret)
    assert backend in (None, "xla"), \
        f"latent attention: no backend {backend!r}"
    assert layer is None, "only the pallas backend reads a whole arena"
    lat = gather_paged_kv(lat_arena, block_table)    # (B, S, W)
    lat = lat.at[jnp.arange(q.shape[0]), cache_len - 1].set(
        new_row.astype(lat.dtype), mode="drop")
    return attend_latent(q, lat, cache_len, scale=scale, dv=dv)


def merge_softmax_states(acc1, m1, l1, acc2, m2, l2):
    """Log-sum-exp merge of two partial online-softmax states.

    Each side carries the flash-attention ``(acc, m, l)`` triple over its
    own key set: ``m = max_j s_j`` (``NEG_INF`` for an empty set),
    ``l = sum_j exp(s_j - m)`` (0 for empty), ``acc = sum_j exp(s_j - m)
    v_j`` (unnormalized; trailing feature axis).  Returns the merged
    triple over the union of the two key sets; the caller normalizes once
    with ``acc / max(l, tiny)``.  An empty side drops out exactly:
    ``exp(NEG_INF - m)`` underflows to zero against a finite ``m``, and
    with both sides empty every term is already zero — so a lane with no
    shared prefix reproduces its suffix-only softmax state bit-for-bit.
    """
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    l = c1 * l1 + c2 * l2
    acc = c1[..., None] * acc1 + c2[..., None] * acc2
    return acc, m, l


def _softmax_state(s, valid):
    """Masked online-softmax state: s (..., S) f32 scores, valid (..., S)
    bool.  Returns (p, m, l) with p the unnormalized probabilities (zero
    where invalid — a fully-masked row yields l == 0, not a uniform
    distribution, which is what lets the merge drop it exactly)."""
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None]) * valid
    return p, m, jnp.sum(p, axis=-1)


def attend_decode_cascade(q, k_arena, v_arena, cascade, cache_len, *,
                          window=0, new_kv=None, scales=None,
                          out_dtype=None, kernel=False,
                          interpret=None) -> jax.Array:
    """Two-level shared-prefix decode attention (flashinfer-style cascade).

    Lanes sharing an indexed radix prefix chain attend it *once as a
    group*: one multi-query pass over the shared prefix KV (gathered once
    per group instead of once per lane), a per-lane pass over each
    divergent suffix, and a log-sum-exp merge of the two partial softmax
    states (:func:`merge_softmax_states`).  ``cascade`` carries the
    host-built group metadata, padded to static bucket shapes:

      group_tables  (G, npre)  int32  shared-prefix block ids, trash-padded
      group_len     (G,)       int32  prefix tokens (multiple of bs; 0 pads)
      group_lanes   (G, Lc)    int32  lane ids per group, 0-padded
      group_mask    (G, Lc)    bool   which lane slots are real
      lane_q0       (B,)       int32  per-lane prefix tokens (0 = ungrouped)
      suffix_tables (B, nsuf)  int32  per-lane divergent-suffix block ids

    Positions ``[0, lane_q0)`` are covered by the lane's group prefix
    pass and ``[lane_q0, cache_len)`` by its suffix pass — disjoint and
    complete, with absolute positions throughout, so the same
    ``cache_len``/``window`` masking as flat :func:`attend_decode_paged`
    selects exactly the same key set.  A window that clips into the
    shared prefix masks the clipped prefix positions inside the group
    pass (per-lane lengths broadcast against the shared keys); a window
    entirely inside the suffix empties the lane's prefix state, which the
    merge then drops exactly.  Scores and accumulators are float32; the
    flat path normalizes *before* its value contraction and this one
    after, so flat-vs-cascade parity is last-ulp tolerance rather than
    bitwise (docs/kvcache.md §Cascade decode — the serving adapter
    degrades to the flat executable when no chain is shared, which *is*
    bitwise).

    ``kernel=True`` runs the three stages through the Pallas kernels
    (``kernels.paged_attn.cascade_prefix_attention`` /
    ``paged_decode_attention_with_state`` / ``merge_attn_states``)
    instead of the XLA math; the kernels-interpret suite pins the two
    against each other.
    """
    assert scales is None, "cascade does not cover the kv_quant layout"
    B, _, Hq, D = q.shape
    Hkv = k_arena.shape[2]
    n_rep = Hq // Hkv
    scale = D ** -0.5
    group_tables = cascade["group_tables"]
    group_len = cascade["group_len"]
    group_lanes = cascade["group_lanes"]
    group_mask = cascade["group_mask"]
    lane_q0 = cascade["lane_q0"]
    suffix_tables = cascade["suffix_tables"]
    G, Lc = group_lanes.shape

    if kernel:
        from repro.kernels import paged_attn as pk
        qg = q[:, 0][group_lanes]                       # (G, Lc, Hq, D)
        lane_len = cache_len[group_lanes]
        acc1g, m1g, l1g = pk.cascade_prefix_attention(
            qg, k_arena, v_arena, group_tables, group_len,
            lane_len.astype(jnp.int32), window=window, interpret=interpret)
        nk = None if new_kv is None else (new_kv[0], new_kv[1])
        acc2, m2, l2 = pk.paged_decode_attention_with_state(
            q[:, 0], k_arena, v_arena, suffix_tables, cache_len,
            window=window, q0=lane_q0, new_kv=nk, interpret=interpret)
    else:
        # -- shared-prefix pass: one gather + one multi-query attention per
        # group; every lane of the group rides in the Lc axis
        qg = q[:, 0][group_lanes].reshape(G, Lc, Hkv, n_rep, D)
        kp = gather_paged_kv(k_arena, group_tables)     # (G, Sp, Hkv, D)
        vp = gather_paged_kv(v_arena, group_tables)
        s1 = jnp.einsum("gchrd,gshd->gchrs", qg, kp,
                        preferred_element_type=jnp.float32) * scale
        posp = jnp.arange(kp.shape[1])
        valid1 = posp[None, None, :] < group_len[:, None, None]  # (G,1,Sp)
        valid1 = jnp.broadcast_to(valid1, (G, Lc, kp.shape[1]))
        if not _static_zero(window):
            lane_len = cache_len[group_lanes]           # (G, Lc)
            valid1 &= posp[None, None, :] >= (lane_len[:, :, None] - window)
        p1, m1g, l1g = _softmax_state(
            s1.astype(jnp.float32), valid1[:, :, None, None, :])
        acc1g = jnp.einsum("gchrs,gshd->gchrd", p1, vp.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        acc1g = acc1g.reshape(G, Lc, Hq, D)
        m1g = m1g.reshape(G, Lc, Hq)
        l1g = l1g.reshape(G, Lc, Hq)
        # -- divergent-suffix pass: per-lane, absolute positions from q0
        qh = q[:, 0].reshape(B, Hkv, n_rep, D)
        ks = gather_paged_kv(k_arena, suffix_tables)    # (B, Ss, Hkv, D)
        vs = gather_paged_kv(v_arena, suffix_tables)
        if new_kv is not None:
            k1, v1 = new_kv
            rows = jnp.arange(B)
            loc = cache_len - 1 - lane_q0
            ks = ks.at[rows, loc].set(k1.astype(ks.dtype), mode="drop")
            vs = vs.at[rows, loc].set(v1.astype(vs.dtype), mode="drop")
        s2 = jnp.einsum("bhrd,bshd->bhrs", qh, ks,
                        preferred_element_type=jnp.float32) * scale
        pos_abs = lane_q0[:, None] + jnp.arange(ks.shape[1])     # (B, Ss)
        valid2 = pos_abs < cache_len[:, None]
        if not _static_zero(window):
            valid2 &= pos_abs >= (cache_len - window)[:, None]
        p2, m2, l2 = _softmax_state(
            s2.astype(jnp.float32), valid2[:, None, None, :])
        acc2 = jnp.einsum("bhrs,bshd->bhrd", p2, vs.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        acc2 = acc2.reshape(B, Hq, D)
        m2 = m2.reshape(B, Hq)
        l2 = l2.reshape(B, Hq)

    # -- scatter group states back to lanes (each real lane sits in exactly
    # one group slot, so the adds are pure placement; padded slots are
    # zeroed / NEG_INF'd here rather than inside the passes)
    flat = group_lanes.reshape(-1)
    fmask = group_mask.reshape(-1)
    acc1 = jnp.zeros((B, Hq, D), jnp.float32).at[flat].add(
        jnp.where(fmask[:, None, None], acc1g.reshape(-1, Hq, D), 0.0))
    l1 = jnp.zeros((B, Hq), jnp.float32).at[flat].add(
        jnp.where(fmask[:, None], l1g.reshape(-1, Hq), 0.0))
    m1 = jnp.full((B, Hq), NEG_INF, jnp.float32).at[flat].max(
        jnp.where(fmask[:, None], m1g.reshape(-1, Hq), NEG_INF))

    if kernel:
        from repro.kernels import paged_attn as pk
        out = pk.merge_attn_states(acc1, m1, l1, acc2, m2, l2,
                                   interpret=interpret)
    else:
        acc, _, l = merge_softmax_states(acc1, m1, l1, acc2, m2, l2)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, Hq, D).astype(out_dtype or v_arena.dtype)
