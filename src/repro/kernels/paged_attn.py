"""Pallas TPU paged decode-attention kernel (single layer, one query token).

The XLA paged path (``nn.attention.attend_decode_paged``) gathers each
request's block chain into a dense (B, S, Hkv, D) array before attending —
at production sizes that materializes the whole cache in HBM every decode
tick.  This kernel reads K/V *directly out of the block arena*: the block
table rides in as a scalar-prefetch operand, so the BlockSpec index map can
route grid step (b, j) at the arena block ``table[b, j]`` and the DMA
engine streams exactly the blocks each request owns — HBM traffic is one
read of the live blocks and nothing else, and the trash block (id 0) that
pads short chains is masked out by ``lens`` like any overlong position.

Grid (B, nb): the trailing dim iterates a request's chain sequentially, so
the online-softmax state (m, l, acc) lives in VMEM scratch across the sweep
— the same structure as ``flash_attn.py`` with the block table supplying
the indirection.  GQA is handled in-kernel: q (Hq, D) is viewed as
(Hkv, n_rep, D) and batched against the block's (Hkv, bs, D) K tile.

Sliding windows ride in as a third scalar-prefetch operand: positions
outside ``[lens - window, lens)`` are masked to NEG_INF exactly like
``attend_decode``'s trailing-window bound (a huge window disables it, which
is also how ``lm.layer_window`` encodes per-layer global attention), so the
serving tick can dispatch every attention family's layers — global and
sliding alike — through one kernel.

Validated in interpret mode against ``attend_decode_paged`` over
shape/dtype/table/window permutations (tests/test_paged_attn.py), and
wired into the serving tick by ``engine.decode_step_paged`` (the
``backend="pallas"`` path of the paged slot adapter).

Latent attention (MLA, Moonlight-16B-A3B) caches one row of latent + RoPE
key per token and layer, no K or V: ``latent_decode_attention`` reads it
as one key head whose first ``dv`` lanes are also the values (each live
row fetched once, several table entries per grid step, entries past a
lane's live blocks neither fetched nor computed), and
``scatter_latent_rows`` lands the tick's one row per layer and lane.

The cascade extension (``backend="cascade"``) splits decode attention over
a shared radix prefix and per-lane divergent suffixes and merges the
partial online-softmax states by log-sum-exp: ``cascade_prefix_attention``
runs one multi-query pass per shared chain (prefix KV streamed once per
*group*, not once per lane), ``paged_decode_attention_with_state`` is this
file's flat sweep restarted at an absolute position offset ``q0`` and
returning its *unnormalized* (acc, m, l) state, and ``merge_attn_states``
fuses the two states and normalizes.  Unlike the flat kernel, the state
kernels zero masked probabilities (``p *= valid``) so an all-masked sweep
yields the empty state (m = NEG_INF, l = 0) that the merge drops exactly —
the flat kernel can leave garbage in fully-masked lanes because it
normalizes in place and its callers mask those lanes out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# a window this large never masks (same encoding as lm._GLOBAL_WINDOW)
NO_WINDOW = 1 << 30


def _paged_kernel(tables_ref, lens_ref, win_ref, layer_ref, q_ref, k_ref,
                  v_ref, *rest, bs: int, nb: int, n_rep: int, scale: float,
                  splice: bool):
    if splice:
        k1_ref, v1_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (Hq, D)
    k = k_ref[0, 0, 0].astype(jnp.float32)        # (bs, Hkv, D)
    v = v_ref[0, 0, 0].astype(jnp.float32)        # (bs, Hkv, D)
    Hq, D = q.shape
    Hkv = k.shape[1]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    if splice:
        # the current token's K/V row, overlaid at its position instead of
        # pre-written into the arena: the sweep reads live blocks only and
        # the arena write stays a single post-scan row per layer.  The
        # mask is built as a (bs, 1, 1) column directly: Mosaic cannot
        # reshape the (1, bs) position row into one
        row = j * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        here = row == lens_ref[b] - 1
        k = jnp.where(here, k1_ref[0].astype(jnp.float32)[None], k)
        v = jnp.where(here, v1_ref[0].astype(jnp.float32)[None], v)
    kt = jnp.swapaxes(k, 0, 1)                    # (Hkv, bs, D)
    qh = q.reshape(Hkv, n_rep, D)
    s = jax.lax.dot_general(qh, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = s.reshape(Hq, bs)
    valid = (pos < lens_ref[b]) & (pos >= lens_ref[b] - win_ref[0])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])               # (Hq, bs)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    vt = jnp.swapaxes(v, 0, 1)                    # (Hkv, bs, D)
    ph = p.reshape(Hkv, n_rep, bs)
    o = jax.lax.dot_general(ph, vt, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + o.reshape(Hq, D)

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _scatter_kernel(wbids_ref, offs_ref, kr_ref, vr_ref, ka_ref, va_ref,
                    ko_ref, vo_ref):
    # grid (L, S): layer l writes lane b's K and V rows at row offs[b] of
    # arena block wbids[b].  The arena refs alias the outputs, so every
    # block not addressed by some (l, b) keeps its bytes untouched — no
    # functional rebuild of the layer slice.  A *visited* block's output
    # window, however, is written back whole at the window switch, so the
    # other bs-1 rows must be seeded from the fetched input block first —
    # without this, Mosaic would write back an uninitialized VMEM window
    # and clobber the live rows the lane already wrote this block
    # (interpret mode masks that, because there the aliased output
    # literally *is* the input buffer).
    b = pl.program_id(1)
    ko_ref[...] = ka_ref[...]
    vo_ref[...] = va_ref[...]
    ko_ref[0, 0, 0, offs_ref[b]] = kr_ref[0, 0]
    vo_ref[0, 0, 0, offs_ref[b]] = vr_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_kv_rows(k_arena, v_arena, k_rows, v_rows, wbids, offs, *,
                    interpret: bool | None = None):
    """Land the decode tick's per-layer K/V rows in the arena **in place**.

    k_arena, v_arena: (L, num_blocks, 1, bs, Hkv, D) — the layer-leading
    ``engine.init_paged_arena`` layout; k_rows, v_rows: (L, S, Hkv, D) the
    new token's post-RoPE rows per layer and lane; wbids: (S,) int32 arena
    block per lane (the caller routes masked lanes to the trash block);
    offs: (S,) int32 row within the block (``len % bs``).

    ``input_output_aliases`` donates both arenas into their outputs: the
    kernel touches exactly the (layer, block) tiles the block table names
    and every other block's bytes stay where they are — the Pallas leg's
    counterpart of the XLA buffer donation that already makes the
    ``.at[].set`` reference leg update in place.  Semantically identical
    to ``arena.at[:, wbids, 0, offs].set(rows)`` wherever the (block,
    row) targets are unique — they are for every live lane; only
    trash-routed lanes may collide, and the trash block's contents are
    garbage under both orders (asserted in tests/test_paged_attn.py).
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    L, nb, _, bs, Hkv, D = k_arena.shape
    S = wbids.shape[0]
    row = pl.BlockSpec((1, 1, Hkv, D), lambda l, b, w, o: (l, b, 0, 0))
    blk = pl.BlockSpec((1, 1, 1, bs, Hkv, D),
                       lambda l, b, w, o: (l, w[b], 0, 0, 0, 0))
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L, S),
            in_specs=[row, row, blk, blk],
            out_specs=[blk, blk],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_arena.shape, k_arena.dtype),
                   jax.ShapeDtypeStruct(v_arena.shape, v_arena.dtype)],
        input_output_aliases={4: 0, 5: 1},
        interpret=interpret,
        name="scatter_kv_rows",
    )(jnp.asarray(wbids, jnp.int32), jnp.asarray(offs, jnp.int32),
      k_rows, v_rows, k_arena, v_arena)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_arena, v_arena, tables, lens, *,
                           window=None, new_kv=None, layer=None,
                           interpret: bool | None = None):
    """q: (B, Hq, D); k_arena, v_arena: (num_blocks, bs, Hkv, D), or with
    ``layer`` (may be traced) the whole ``(L, num_blocks, 1, bs, Hkv, D)``
    arena of ``engine.init_paged_arena``, read at that layer: the serving
    tick hands the arena over as it lies in HBM, since slicing one layer
    out of it inside the layer scan would copy every block, live or not,
    once per layer;
    tables: (B, nb) int32 arena block ids; lens: (B,) int32 valid lengths.
    ``window``: optional scalar (may be traced — the per-layer
    sliding/global selection is data-dependent inside a layer scan); only
    the trailing ``window`` positions attend.  None or 0 disables masking.
    ``new_kv``: optional (k1, v1), each (B, Hkv, D) — the current token's
    K/V row, overlaid in-kernel at position ``lens - 1`` so the serving
    tick never has to pre-write the row into the arena (a functional
    arena-slice update per layer would copy every block, live or not —
    exactly the traffic this kernel exists to avoid).
    Returns (B, Hq, D) in v_arena.dtype.
    ``interpret=None`` auto-detects the backend (Mosaic on TPU only).
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Hq, D = q.shape
    if layer is None:               # one layer's arena, read as L = 1
        k_arena, v_arena, layer = k_arena[None, :, None], \
            v_arena[None, :, None], 0
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    bs, Hkv = k_arena.shape[3:5]
    nb = tables.shape[1]
    n_rep = Hq // Hkv
    scale = D ** -0.5
    if window is None:
        window = NO_WINDOW
    win = jnp.where(jnp.asarray(window, jnp.int32) == 0, NO_WINDOW,
                    jnp.asarray(window, jnp.int32)).reshape(1)
    row = pl.BlockSpec((1, Hq, D), lambda b, j, t, ln, w, y: (b, 0, 0))
    blk = pl.BlockSpec((1, 1, 1, bs, Hkv, D),
                       lambda b, j, t, ln, w, y: (y[0], t[b, j], 0, 0, 0, 0))
    kv_row = pl.BlockSpec((1, Hkv, D), lambda b, j, t, ln, w, y: (b, 0, 0))
    splice = new_kv is not None
    operands = (jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32),
                win, lyr, q, k_arena, v_arena)
    in_specs = [row, blk, blk]
    if splice:
        operands += tuple(new_kv)
        in_specs += [kv_row, kv_row]
    return pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, nb=nb, n_rep=n_rep,
                          scale=scale, splice=splice),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hq, D),
                                   lambda b, j, t, ln, w, y: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((Hq,), jnp.float32),      # running max
                pltpu.VMEM((Hq,), jnp.float32),      # running sum
                pltpu.VMEM((Hq, D), jnp.float32),    # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), v_arena.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(*operands)


# table entries one grid step of the latent sweep reads
LATENT_TILES = 8


def _latent_kernel(tables_ref, lens_ref, layer_ref, q_ref, *rest, bs: int,
                   tiles: int, steps: int, scale: float, dv: int):
    lat_refs, row_ref = rest[:tiles], rest[tiles]
    o_ref, m_scr, l_scr, acc_scr = rest[tiles + 1:]
    b = pl.program_id(0)
    j = pl.program_id(1)
    n = lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (Hq, W)
    new = row_ref[0].astype(jnp.float32)          # (1, W)
    for t, lat_ref in enumerate(lat_refs):
        base = (j * tiles + t) * bs

        @pl.when(base < n)
        def _tile():
            # the new token's row is overlaid at its position: it reaches
            # the arena after the layer scan
            row = base + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
            lat = jnp.where(row == n - 1, new,
                            lat_ref[0, 0, 0].astype(jnp.float32))  # (bs, W)
            s = jax.lax.dot_general(q, lat, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            pos = base + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
            s = jnp.where(pos < n, s * scale, NEG_INF)          # (Hq, bs)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m_prev - m_new)
            l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
            m_scr[...] = m_new
            o = jax.lax.dot_general(p, lat[:, :dv], (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc_scr[...] = acc_scr[...] * corr[:, None] + o

    @pl.when(j == steps - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "dv", "interpret"))
def latent_decode_attention(q, lat_arena, tables, lens, *, new_row, scale,
                            dv, layer, interpret: bool | None = None):
    """Latent (MLA) decode attention read in place from the paged arena:
    one key head of width W whose first ``dv`` lanes are also the values,
    so each live row is read once.

    q: (B, Hq, W) absorbed queries; lat_arena: the whole
    ``(L, num_blocks, 1, bs, W)`` latent arena, read at ``layer`` (may be
    traced) as it lies in HBM; tables (B, nb);
    lens (B,) valid rows including the new one, whose row ``new_row``
    (B, W) is overlaid at ``lens - 1``; ``scale`` the softmax scale.
    Returns (B, Hq, dv) in the arena's dtype.

    Grid (B, ceil(nb / LATENT_TILES)): each step reads ``LATENT_TILES``
    table entries of a lane.  An entry past the lane's last live block is
    mapped to that block again, which the pipeline does not fetch twice,
    and its compute is skipped: a lane costs its live blocks, not
    ``max_len``.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Hq, W = q.shape
    bs = lat_arena.shape[3]
    g = LATENT_TILES
    steps = -(-tables.shape[1] // g)
    tables = jnp.pad(jnp.asarray(tables, jnp.int32),
                     ((0, 0), (0, steps * g - tables.shape[1])))
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def tile(t):
        def index(b, j, tb, ln, y):
            last = (ln[b] - 1) // bs
            return (y[0], tb[b, jnp.minimum(j * g + t, last)], 0, 0, 0)
        return pl.BlockSpec((1, 1, 1, bs, W), index)
    per_lane = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda b, j, tb, ln, y: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, tiles=g, steps=steps,
                          scale=scale, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, steps),
            in_specs=[per_lane(Hq, W)] + [tile(t) for t in range(g)]
            + [per_lane(1, W)],
            out_specs=per_lane(Hq, dv),
            scratch_shapes=[
                pltpu.VMEM((Hq,), jnp.float32),      # running max
                pltpu.VMEM((Hq,), jnp.float32),      # running sum
                pltpu.VMEM((Hq, dv), jnp.float32),   # output accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, dv), lat_arena.dtype),
        interpret=interpret,
        name="latent_decode_attention",
    )(tables, jnp.asarray(lens, jnp.int32), lyr, q,
      *([lat_arena] * g), new_row[:, None])


def _scatter_latent_kernel(wbids_ref, offs_ref, r_ref, a_ref, o_ref, *,
                           bs: int):
    # grid (L, S): layer l writes lane b's latent row at row offs[b] of
    # arena block wbids[b]; the aliased output keeps every other block's
    # bytes, and the visited block's other rows come from its input
    b = pl.program_id(1)
    row = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    o_ref[0, 0, 0] = jnp.where(row == offs_ref[b], r_ref[0, 0],
                               a_ref[0, 0, 0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def scatter_latent_rows(arena, rows, wbids, offs, *,
                        interpret: bool | None = None):
    """Land the decode tick's latent rows in the arena **in place**: one
    row per layer and lane, where :func:`scatter_kv_rows` lands a K/V pair.

    arena: (L, num_blocks, 1, bs, W); rows: (L, S, W); wbids, offs: (S,)
    int32 block and row within it per lane (masked lanes go to the trash
    block).  Equal to ``arena.at[:, wbids, 0, offs].set(rows)`` wherever
    the (block, row) targets are unique."""
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    L, _, _, bs, W = arena.shape
    S = wbids.shape[0]
    blk = pl.BlockSpec((1, 1, 1, bs, W),
                       lambda l, b, w, o: (l, w[b], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_scatter_latent_kernel, bs=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L, S),
            in_specs=[pl.BlockSpec((1, 1, 1, W),
                                   lambda l, b, w, o: (l, b, 0, 0)), blk],
            out_specs=blk,
        ),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="scatter_latent_rows",
    )(jnp.asarray(wbids, jnp.int32), jnp.asarray(offs, jnp.int32),
      rows[:, :, None], arena)


def _paged_state_kernel(tables_ref, lens_ref, win_ref, q0_ref, q_ref, k_ref,
                        v_ref, *rest, bs: int, nb: int, n_rep: int,
                        scale: float, splice: bool):
    if splice:
        k1_ref, v1_ref, acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    else:
        acc_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (Hq, D)
    k = k_ref[0].astype(jnp.float32)              # (bs, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    Hq, D = q.shape
    Hkv = k.shape[1]
    # absolute positions: this sweep covers [q0, q0 + nb*bs) of the chain
    pos = q0_ref[b] + j * bs + \
        jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    if splice:
        row = q0_ref[b] + j * bs + \
            jax.lax.broadcasted_iota(jnp.int32, (bs, 1, 1), 0)
        here = row == lens_ref[b] - 1
        k = jnp.where(here, k1_ref[0].astype(jnp.float32)[None], k)
        v = jnp.where(here, v1_ref[0].astype(jnp.float32)[None], v)
    kt = jnp.swapaxes(k, 0, 1)                    # (Hkv, bs, D)
    qh = q.reshape(Hkv, n_rep, D)
    s = jax.lax.dot_general(qh, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = s.reshape(Hq, bs)
    valid = (pos < lens_ref[b]) & (pos >= lens_ref[b] - win_ref[0])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # unlike the flat kernel, zero masked probabilities: an all-masked
    # sweep must return the EMPTY state (m = NEG_INF, l = 0) — with both
    # operands at NEG_INF, exp(s - m) is exp(0) = 1 per position, which
    # would poison the cascade merge with a phantom uniform distribution
    p = jnp.exp(s - m_new[:, None]) * valid.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    vt = jnp.swapaxes(v, 0, 1)                    # (Hkv, bs, D)
    ph = p.reshape(Hkv, n_rep, bs)
    o = jax.lax.dot_general(ph, vt, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + o.reshape(Hq, D)

    @pl.when(j == nb - 1)
    def _finish():
        acc_ref[0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_with_state(q, k_arena, v_arena, tables, lens, *,
                                      window=None, q0=None, new_kv=None,
                                      interpret: bool | None = None):
    """The flat paged sweep, restarted at an offset and left unnormalized.

    Same operands as :func:`paged_decode_attention` plus ``q0``: (B,)
    int32 absolute position of each lane's first table entry — the table
    names the lane's *divergent suffix* blocks and positions are
    ``q0[b] + j*bs + i``, so the ``lens``/``window`` bounds select exactly
    the suffix share of the flat kernel's key set (the group prefix pass
    covers ``[0, q0)``; disjoint and complete).  Returns the float32
    online-softmax state ``(acc (B, Hq, D), m (B, Hq), l (B, Hq))`` for
    :func:`merge_attn_states`; the new-token row still splices at
    ``lens - 1``, which always falls in the suffix (the shared prefix is
    full blocks only).
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Hq, D = q.shape
    _, bs, Hkv, _ = k_arena.shape
    nb = tables.shape[1]
    n_rep = Hq // Hkv
    scale = D ** -0.5
    if window is None:
        window = NO_WINDOW
    win = jnp.where(jnp.asarray(window, jnp.int32) == 0, NO_WINDOW,
                    jnp.asarray(window, jnp.int32)).reshape(1)
    if q0 is None:
        q0 = jnp.zeros((B,), jnp.int32)
    row = pl.BlockSpec((1, Hq, D), lambda b, j, t, ln, w, z: (b, 0, 0))
    # m and l leave as (B, 1, Hq): a (1, Hq) block of a (B, Hq) array is
    # neither 8-row aligned nor the whole array, which Mosaic refuses
    hrow = pl.BlockSpec((1, 1, Hq), lambda b, j, t, ln, w, z: (b, 0, 0))
    blk = pl.BlockSpec((1, bs, Hkv, D),
                       lambda b, j, t, ln, w, z: (t[b, j], 0, 0, 0))
    kv_row = pl.BlockSpec((1, Hkv, D), lambda b, j, t, ln, w, z: (b, 0, 0))
    splice = new_kv is not None
    operands = (jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32),
                win, jnp.asarray(q0, jnp.int32), q, k_arena, v_arena)
    in_specs = [row, blk, blk]
    if splice:
        operands += tuple(new_kv)
        in_specs += [kv_row, kv_row]
    acc, m, l = pl.pallas_call(
        functools.partial(_paged_state_kernel, bs=bs, nb=nb, n_rep=n_rep,
                          scale=scale, splice=splice),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, nb),
            in_specs=in_specs,
            out_specs=[row, hrow, hrow],
            scratch_shapes=[
                pltpu.VMEM((Hq,), jnp.float32),      # running max
                pltpu.VMEM((Hq,), jnp.float32),      # running sum
                pltpu.VMEM((Hq, D), jnp.float32),    # output accumulator
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, Hq), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, Hq), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return acc, m[:, 0], l[:, 0]


def _cascade_prefix_kernel(tables_ref, glen_ref, win_ref, ll_ref, q_ref,
                           k_ref, v_ref, acc_ref, m_ref, l_ref, m_scr,
                           l_scr, acc_scr, *, bs: int, nb: int, n_rep: int,
                           scale: float):
    g = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # (Lc, Hq, D)
    k = k_ref[0].astype(jnp.float32)              # (bs, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    Lc, Hq, D = q.shape
    Hkv = k.shape[1]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    # one key set per group, one validity row per lane: the chain bound is
    # shared (group_len), the window bound is each lane's own length
    valid = pos < glen_ref[g]                                  # (1, 1, bs)
    lane_len = ll_ref[0][:, :, None]                           # (Lc, 1, 1)
    valid = valid & (pos >= lane_len - win_ref[0])             # (Lc, 1, bs)

    kt = jnp.swapaxes(k, 0, 1)                    # (Hkv, bs, D)
    qh = q.reshape(Lc, Hkv, n_rep, D).transpose(1, 0, 2, 3) \
        .reshape(Hkv, Lc * n_rep, D)
    s = jax.lax.dot_general(qh, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = s.reshape(Hkv, Lc, n_rep, bs).transpose(1, 0, 2, 3) \
        .reshape(Lc, Hq, bs)
    valid = jnp.broadcast_to(valid, (Lc, Hq, bs))
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None]) * valid.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    vt = jnp.swapaxes(v, 0, 1)                    # (Hkv, bs, D)
    ph = p.reshape(Lc, Hkv, n_rep, bs).transpose(1, 0, 2, 3) \
        .reshape(Hkv, Lc * n_rep, bs)
    o = jax.lax.dot_general(ph, vt, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    o = o.reshape(Hkv, Lc, n_rep, D).transpose(1, 0, 2, 3) \
        .reshape(Lc, Hq, D)
    acc_scr[...] = acc_scr[...] * corr[..., None] + o

    @pl.when(j == nb - 1)
    def _finish():
        acc_ref[0] = acc_scr[...]
        m_ref[0] = m_scr[...]
        l_ref[0] = l_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cascade_prefix_attention(qg, k_arena, v_arena, group_tables, group_len,
                             lane_lens, *, window=None,
                             interpret: bool | None = None):
    """One multi-query pass per shared-prefix chain.

    qg: (G, Lc, Hq, D) the grouped lanes' query rows; group_tables:
    (G, npre) int32 shared chain block ids (trash-padded); group_len: (G,)
    int32 prefix tokens (0 for pad groups — their state comes back empty);
    lane_lens: (G, Lc) int32 each lane's cache length, which anchors the
    sliding-window bound ``pos >= lane_len - window`` when the window
    clips into the shared prefix.  Grid (G, npre): each chain's KV
    streams out of the arena ONCE and every lane of the group attends it
    from the Lc axis.  Returns float32 ``(acc (G, Lc, Hq, D), m, l
    (G, Lc, Hq))`` — unnormalized, for :func:`merge_attn_states` after
    the caller scatters group slots back to lanes.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    G, Lc, Hq, D = qg.shape
    _, bs, Hkv, _ = k_arena.shape
    nb = group_tables.shape[1]
    n_rep = Hq // Hkv
    scale = D ** -0.5
    if window is None:
        window = NO_WINDOW
    win = jnp.where(jnp.asarray(window, jnp.int32) == 0, NO_WINDOW,
                    jnp.asarray(window, jnp.int32)).reshape(1)
    qrow = pl.BlockSpec((1, Lc, Hq, D), lambda g, j, t, gl, w: (g, 0, 0, 0))
    # lane lens enter as (G, Lc, 1): a (1, Lc) block of a (G, Lc) array is
    # neither 8-row aligned nor the whole array, which Mosaic refuses
    lrow = pl.BlockSpec((1, Lc, 1), lambda g, j, t, gl, w: (g, 0, 0))
    blk = pl.BlockSpec((1, bs, Hkv, D),
                       lambda g, j, t, gl, w: (t[g, j], 0, 0, 0))
    grow = pl.BlockSpec((1, Lc, Hq, D), lambda g, j, t, gl, w: (g, 0, 0, 0))
    hrow = pl.BlockSpec((1, Lc, Hq), lambda g, j, t, gl, w: (g, 0, 0))
    return pl.pallas_call(
        functools.partial(_cascade_prefix_kernel, bs=bs, nb=nb, n_rep=n_rep,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G, nb),
            in_specs=[lrow, qrow, blk, blk],
            out_specs=[grow, hrow, hrow],
            scratch_shapes=[
                pltpu.VMEM((Lc, Hq), jnp.float32),     # running max
                pltpu.VMEM((Lc, Hq), jnp.float32),     # running sum
                pltpu.VMEM((Lc, Hq, D), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((G, Lc, Hq, D), jnp.float32),
                   jax.ShapeDtypeStruct((G, Lc, Hq), jnp.float32),
                   jax.ShapeDtypeStruct((G, Lc, Hq), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(group_tables, jnp.int32), jnp.asarray(group_len, jnp.int32),
      win, jnp.asarray(lane_lens, jnp.int32).reshape(G, Lc, 1), qg, k_arena,
      v_arena)


def _merge_kernel(acc1_ref, m1_ref, l1_ref, acc2_ref, m2_ref, l2_ref, o_ref):
    m1 = m1_ref[0, 0]
    m2 = m2_ref[0, 0]
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    l = c1 * l1_ref[0, 0] + c2 * l2_ref[0, 0]
    acc = c1[:, None] * acc1_ref[0] + c2[:, None] * acc2_ref[0]
    o_ref[0] = acc / jnp.maximum(l, 1e-30)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def merge_attn_states(acc1, m1, l1, acc2, m2, l2, *,
                      interpret: bool | None = None):
    """Log-sum-exp merge of two partial softmax states, then normalize.

    acc: (B, Hq, D) float32 unnormalized accumulators; m, l: (B, Hq)
    float32 running max / sum.  The Pallas counterpart of
    ``nn.attention.merge_softmax_states`` + the final ``acc / max(l,
    tiny)`` division: an empty side (m = NEG_INF, l = 0) drops out
    through exp underflow, both sides empty yields zeros.  Returns
    (B, Hq, D) float32.
    """
    from repro.kernels.ops import resolve_interpret
    interpret = resolve_interpret(interpret)
    B, Hq, D = acc1.shape
    arow = pl.BlockSpec((1, Hq, D), lambda b: (b, 0, 0))
    # m and l enter as (B, 1, Hq), the layout the state kernel writes
    hrow = pl.BlockSpec((1, 1, Hq), lambda b: (b, 0, 0))
    col = lambda a: a.reshape(B, 1, Hq)
    return pl.pallas_call(
        _merge_kernel,
        grid=(B,),
        in_specs=[arow, hrow, hrow, arow, hrow, hrow],
        out_specs=arow,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), jnp.float32),
        interpret=interpret,
    )(acc1, col(m1), col(l1), acc2, col(m2), col(l2))
