"""Block-table-backed KV slots: the paged counterpart of ``KVSlotAdapter``.

Layout
    One preallocated device arena per sequence-axis cache key —
    ``arena[key]: (num_blocks,) + B=1 block shape`` from
    :func:`engine.init_paged_arena` — shared by every slot.  Each slot holds
    a block table (row of ``(n_slots, nb_max)`` int32) mapping logical block
    j to an arena block id; non-sequence state (rwkv-style taps, ssm/conv,
    encoder cross K/V, ``len``) stays densely slot-stacked exactly as in the
    dense adapter.

Decode tick (one jitted call, fixed shapes)
    The default tick is **in place**: :func:`engine.decode_step_paged`
    threads ``(tables, lens, arena)`` down into the attention layers, which
    read K/V straight out of the block arena (``attend_decode_paged`` in
    XLA, or the ``kernels/paged_attn.py`` scalar-prefetch kernel under
    ``backend="pallas"``) and write back exactly one row per layer — the
    new token's position.  No dense per-slot cache is ever materialized and
    no block is rescattered.  Inactive lanes write into the reserved trash
    block 0, so the call never changes shape.  Because every position a
    lane can read (< len) holds the same bits in both layouts and
    everything else is masked at NEG_INF before the softmax, in-place
    paged decode is *bitwise* identical to dense decode — pinned per
    family in tests/test_paged_decode.py.  Both run one layer stack
    (``engine._decode_layers``); only the cache plumbing differs.

    The gather tick (gather each chain into the dense layout -> vmapped
    :func:`engine.decode_step` -> scatter one block back) is kept as
    ``backend="gather"``: it is the parity oracle the in-place path is
    asserted bitwise against, and nothing else.  The in-place tick covers
    every paged family — vlm's grouped cache (two leading layer axes)
    included, so decode slices of the disaggregated mesh never need the
    gather path.  The int8 ``kv_quant`` layout rides the in-place tick as
    well: the new row is quantized post-RoPE and written as one int8 row +
    one f32 scale row per layer, and the attention read dequantizes the
    gathered view — bitwise against the gather-tick oracle.

    Under the Pallas backend the arena lies in HBM in the layout the
    kernels read (row-major, :func:`arena_formats`), every program that
    takes it declares that format, and the kernels get the whole
    layer-leading arena plus a layer index: the compiled tick holds no
    relayout and no per-layer slice of the arena.

Sharing / copy-on-write
    Admission walks the pool's radix index: full prompt blocks that match an
    earlier request's chain are referenced instead of written (their prefill
    values are discarded).  A trailing partial prompt block can be shared
    too when the whole chain plus the partial chunk matches; since decode
    extends partial blocks in place, every holder of a shared partial block
    carries a pre-allocated *spare* and copies into it before its first
    write (copy-on-write) — the sibling keeps the original, bit-for-bit.

Chunked prefill (prefix-hit compute skipping)
    Admission prefills a prompt as a *fold* of fixed block-size chunks
    through :func:`engine.prefill_chunked` — chunk j extends the KV prefix
    of j*bs positions by one block.  A radix prefix hit of H blocks gathers
    those blocks from the arena and resumes the fold at chunk H: the shared
    prompt's transformer work is skipped, not just its storage.  Chunk j's
    compiled graph has the same static shapes whether the fold started at
    0 or resumed at H, so a resumed prefill is *bitwise* identical to the
    cold one — same logits, same written blocks.  Hybrid (SSM) resumption
    additionally needs the recurrent state at the boundary; the fold
    snapshots it per indexed chain key (dropped when the pool unindexes the
    key), and falls back to an earlier boundary (or a cold fold) when the
    snapshot is gone.  ``chunked=False`` keeps the one-shot prefill path of
    PR 2 (share storage, recompute everything; lazy copy-on-write).

Admission control
    ``can_admit`` prices a request at its worst case,
    ``ceil((P + max_new) / bs)`` blocks minus full-prefix hits, and admits
    only when the pool's free + evictable supply covers it — the batcher
    queues the request otherwise instead of letting an allocation fail
    mid-flight.  The boundary (partial) block is priced once: under the
    chunked fold the slot recomputes it into its own spare (already inside
    ``n_total - hits``) without ever referencing the shared partial, while
    the legacy path additionally holds the shared partial (its LRU revival
    consumes supply) and may oblige existing holders to take copy-on-write
    spares — see ``_admission_demand``.
"""
from __future__ import annotations

import contextlib
import math
from collections import OrderedDict
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.models.lm import LMConfig
from repro.serve import engine
from repro.serve.backend import resolve_backend
from repro.serve.kvcache.pool import (TRASH_BLOCK, BlockPool, PoolExhausted)
from repro.serve.obs.tracer import NO_SPAN


def _pad_seq(a: jax.Array, target: int, key: str) -> jax.Array:
    """Zero-pad the sequence axis of cache key ``key`` to ``target``."""
    ax = engine.seq_axis(key)
    pad = [(0, 0)] * a.ndim
    pad[ax] = (0, target - a.shape[ax])
    return jnp.pad(a, pad)


# Process-wide chunked-prefill fold executables, one per LMConfig (frozen,
# hashable).  jit buckets specialize per (q_offset, chunk/prefix shape) —
# the *same* fixed bucket set for every adapter of a config, so spinning up
# a second adapter (a second gateway slice, a test fixture, an A/B config)
# reuses the first one's compilations instead of re-tracing them all.
# tests/test_chunked_prefill.py asserts no steady-state recompiles across
# two adapters of one config.
_CHUNK_FOLDS: dict[LMConfig, Callable] = {}


def chunk_fold_fn(cfg: LMConfig) -> Callable:
    """The shared jitted ``engine.prefill_chunked`` step for ``cfg``."""
    fn = _CHUNK_FOLDS.get(cfg)
    if fn is None:
        def prefill_chunk(p, batch, cache, q):
            return engine.prefill_chunked(cfg, p, batch, cache, q)
        fn = jax.jit(prefill_chunk, static_argnums=(3,))
        _CHUNK_FOLDS[cfg] = fn
    return fn


def arena_formats(arena, backend: str, shardings: dict) -> dict | None:
    """The HBM format every program that takes the arena declares for it,
    per key, or None for the compiler's default layout.

    On the TPU the default layout of an array whose minor dim is not a
    multiple of the 128 lanes (head dims 80 and 64) puts the block axis
    minor-most, sparing the head dim its padding.  The Pallas kernels read
    and write the arena row-major, so under that default the compiled tick
    relayouts the whole arena into row-major and back around them, and each
    layer's slice once more, every tick.  Where a Pallas kernel touches the
    arena (``backend="pallas"``) it is therefore held row-major — the head
    dim padded to 128 lanes — by every program that takes it; the XLA reads
    of the other backends suit the compact default and keep it.  On the CPU
    the default layout is row-major already.  ``arena`` holds arrays or
    ShapeDtypeStructs; ``shardings`` maps each key to its placement."""
    if backend != "pallas":
        return None
    return {key: Format(Layout(tuple(range(a.ndim))), shardings[key])
            for key, a in arena.items()}


@contextlib.contextmanager
def _not_persisted():
    """Nothing compiled inside enters JAX's persistent compile cache."""
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", math.inf)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was)


def _unpersisted(fn):
    """``fn``, a jitted program that returns the pinned arena, with every
    compile it makes kept out of JAX's persistent compile cache.  An
    executable loaded from that cache labels its outputs with the default
    layout whatever layout it wrote them in (jax 0.9.0): a warm process
    would misread the arena, or see the next program that declares its
    format refuse it.  Such programs compile afresh in every process (the
    full-width tick in about 2 s).  jit compiles inside the call that
    misses its cache, so the call is the compile's scope, and so is
    ``lower(...).compile()``.  Drop this once jax keeps the layout."""
    def call(*args, **kw):
        with _not_persisted():
            out = fn(*args, **kw)
        if isinstance(out, jax.stages.Lowered):
            out.compile = _unpersisted(out.compile)
        return out
    if hasattr(fn, "lower"):
        # through a Python closure, never the jitted function's own bound
        # ``_cache_size``: that binding hides ``fn`` from the garbage
        # collector, so the cycle adapter -> call -> fn -> bound method ->
        # adapter was never collected and every Pallas adapter kept its
        # params and arena on the device for the life of the process
        call.lower = _unpersisted(fn.lower)
        call._cache_size = lambda: fn._cache_size()
    return call


def jit_arena(fn, fmt: dict | None, n_args: int, *, arena_arg: int = 0,
              out=None, **jit_kw):
    """``jax.jit(fn, **jit_kw)`` with the arena argument (position
    ``arena_arg`` of ``n_args``) and, through ``out(fmt)``, the arena in
    the result held to ``fmt`` (:func:`arena_formats`).  A donated arena
    then aliases in place with no change of layout, and an arena in any
    other layout is refused, not relayouted.  A program that returns the
    arena is :func:`_unpersisted`.  ``fmt=None`` declares nothing."""
    if fmt is None:
        return jax.jit(fn, **jit_kw)
    jit_kw["in_shardings"] = tuple(fmt if i == arena_arg else None
                                   for i in range(n_args))
    if out is None:
        return jax.jit(fn, **jit_kw)
    jit_kw["out_shardings"] = out(fmt)
    return _unpersisted(jax.jit(fn, **jit_kw))


def decode_tick(cfg: LMConfig, max_len: int, backend: str,
                fmt: dict | None = None, interpret: bool | None = None):
    """The adapter's gather-free decode tick under ``backend`` ("xla",
    "pallas" or "cascade"), jitted with the arena and slot state donated
    and the arena held to ``fmt``: ``(params, arena, dense, tables, tokens,
    mask, wbids[, cascade]) -> (arena, dense, logits)``.

    :func:`engine.decode_step_paged` reads K/V through the block tables
    inside every attention layer and writes back one row per layer — no
    dense per-slot cache, no block rescatter.  Non-sequence state is masked
    exactly like the gather tick, so inactive lanes keep the state
    ``clear`` left them.  Under "cascade", ``cascade`` is the host-built
    group metadata of :meth:`PagedKVSlotAdapter._cascade_meta`: every
    attention layer reads each shared radix chain once per group and
    merges the partial softmax states by log-sum-exp
    (:func:`nn.attention.attend_decode_cascade`); the write epilogue is the
    flat tick's."""
    def _tick_inplace_impl(p, arena, dense, tables, tokens, mask, wbids,
                           cascade=None):
        # a lane whose len escaped the table (at capacity / accounting
        # drift) must write the trash block, never a real — possibly
        # shared — one (the gather tick's defense too)
        wbids = jnp.where(dense["len"] >= max_len, TRASH_BLOCK, wbids)
        new_arena, new_cache, logits = engine.decode_step_paged(
            cfg, p, dense, tokens, tables=tables, lens=dense["len"],
            arena=arena, wbids=wbids, backend=backend, cascade=cascade,
            interpret=interpret)
        sel = lambda new, old: jnp.where(
            mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
        new_dense = {key: sel(new_cache[key], dense[key]) for key in dense}
        return new_arena, new_dense, logits
    # each tick keeps its own program name in traces
    if backend == "cascade":
        _tick_inplace_impl.__name__ = "_tick_cascade_impl"
        _tick_inplace_impl.__qualname__ = "_tick_cascade_impl"
    return jit_arena(_tick_inplace_impl, fmt,
                     8 if backend == "cascade" else 7, arena_arg=1,
                     out=lambda f: (f, None, None), donate_argnums=(1, 2))


class PagedKVSlotAdapter:
    """Paged KV slots for the attention families (decoder/moe/hybrid/
    encdec/vlm).

    Drop-in for ``KVSlotAdapter`` in :class:`ContinuousBatcher` (same
    ``insert`` / ``decode`` / ``clear`` surface), plus the paging hooks the
    batcher discovers by presence: ``can_admit``, ``validate_request``,
    ``slot_stats``, ``pool_stats``.
    """

    def __init__(self, cfg: LMConfig, params, n_slots: int, max_len: int,
                 *, block_size: int = 16, num_blocks: int | None = None,
                 extras: Callable[[], dict] | None = None,
                 chunked: bool = True, inplace: bool | None = None,
                 kernel: bool | None = None, mesh=None,
                 backend: str | None = None):
        assert cfg.family != "rwkv", "rwkv has O(1) state; nothing to page"
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.bs = block_size
        self.nb_max = -(-max_len // block_size)
        self.max_len = self.nb_max * block_size
        self.extras = extras
        # chunked prefill needs the pre-quantization KV the int8 cache no
        # longer holds, and a family prefill_chunked implements
        self.chunked = (chunked and not cfg.kv_quant and cfg.family in
                        ("decoder", "moe", "hybrid", "encdec"))
        # one backend enum ("gather" | "xla" | "pallas" | "cascade", see
        # repro.serve.backend) replaces the inplace=/kernel= booleans,
        # which survive as deprecated aliases (warned once, here).  The
        # in-place tick covers every paged family — incl. the int8
        # kv_quant layout (quantized one-row write + dequantize-in-tick)
        # and, since PR 8, vlm's grouped cache; the PR 2 gather tick
        # stays available purely as the parity oracle.  The Pallas kernel
        # and the cascade grouping do NOT cover kv_quant or vlm: the
        # platform auto-selection quietly falls back to XLA there, but an
        # *explicit* backend choice is a contract ("forces the path") and
        # must fail loudly rather than measure the wrong one.
        explicit = backend is not None or kernel
        self.backend = resolve_backend(backend, inplace=inplace,
                                       kernel=kernel, warn=True)
        if (self.backend in ("pallas", "cascade") and
                (cfg.kv_quant or cfg.family == "vlm")) or \
                (self.backend == "cascade" and cfg.mla):
            layout = "int8 kv_quant" if cfg.kv_quant else \
                "vlm grouped" if cfg.family == "vlm" else "latent"
            if explicit:
                raise ValueError(
                    f"backend={self.backend!r} does not support the "
                    f"{layout} layout; use backend=\"xla\"")
            self.backend = "xla"
        self.inplace = self.backend != "gather"
        self.kernel = self.backend == "pallas"
        if num_blocks is None:
            # dense-equivalent capacity + the reserved trash block
            num_blocks = n_slots * self.nb_max + 1
        self.pool = BlockPool(num_blocks, block_size)
        # mesh-partitioned placement (serve/shard/): commit the arena to
        # the slice mesh with engine.arena_specs (KV heads over "model"
        # when divisible — the same rule cache_specs applies to the dense
        # layout) and replicate params + the slot-stacked state across the
        # slice's devices.  Every jit below then compiles *sharded* —
        # GSPMD partitions the tick/fold over the slice — while a
        # single-device slice runs the exact unsharded executable (the
        # bitwise-parity contract tests/test_sharded.py pins).  A pinned
        # arena format (arena_formats) commits the arena to the default
        # device.  Whatever is committed, the slot-stacked state and the
        # cold fold's inputs are committed beside it (``self.placement``):
        # a program fed committed arrays on one call and uncommitted ones
        # on the next compiles twice.  The arena is made by one program
        # straight in its placement and format, so the device never holds
        # it twice.
        self.mesh = mesh
        shape = engine.init_paged_arena(cfg, num_blocks, block_size,
                                        abstract=True)
        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from repro.dist.sharding import mesh_shape_dict
            specs = engine.arena_specs(cfg, mesh_shape_dict(mesh))
            where = {key: NamedSharding(mesh, specs[key]) for key in shape}
            self.placement = NamedSharding(mesh, P())
        else:
            self.placement = SingleDeviceSharding(jax.devices()[0])
            where = dict.fromkeys(shape, self.placement)
        self.arena_format = arena_formats(shape, self.backend, where)
        if mesh is None and self.arena_format is None:
            self.placement = None
        make = lambda: engine.init_paged_arena(cfg, num_blocks, block_size)
        if self.placement is None:
            self.arena = make()
        else:
            self.arena = _unpersisted(jax.jit(
                make, out_shardings=self.arena_format or where))()
        self.seq_keys = tuple(self.arena)
        self._bax = {key: engine.arena_block_axis(a, key)
                     for key, a in self.arena.items()}
        # hybrid: recurrent (conv/ssm) state at each indexed block boundary,
        # keyed by the boundary's chain key — what lets an SSM stream resume
        # mid-prompt; invalidated with the index entry itself.  Entries are
        # naturally bounded by the indexed-key count (<= pool capacity), and
        # an explicit LRU cap keeps the side cache's bytes proportional to
        # the arena budget even so (evicting one only costs a longer
        # re-fold); pool_stats reports the bytes it holds.
        self._boundary_states: "OrderedDict[bytes, dict]" = OrderedDict()
        self._max_boundary_states = self.pool.capacity
        self.pool.on_unindex = \
            lambda bid, key: self._boundary_states.pop(key, None)
        # compute-skip telemetry (prefill_tokens_* in pool_stats)
        self.prefill_tokens_total = 0
        self.prefill_tokens_skipped_total = 0
        # decode work of each tick as ``engine.decode_moe_work`` (MoE) and
        # ``engine.decode_attn_work`` (the dense Pallas attention) count it
        # from shapes: per tick in ``last_work`` (the tick spans' args),
        # and running totals
        self.moe_rows_total = 0
        self.moe_picks_total = 0
        self.attn_blocks_read_total = 0
        self.attn_blocks_live_total = 0
        self.last_work = {}
        # obs span recorder, wired by the prompt gateways for a run's
        # duration; every use is guarded so a bare adapter makes zero
        # obs calls.  The batcher points the tracer's lane context at the
        # admitting request before insert, so chunk spans land on it.
        self.tracer = None

        # densely slot-stacked non-sequence state (incl. the scalar "len")
        cache0 = engine.init_cache(cfg, 1, self.max_len)
        self.cache = self._place({
            key: jnp.zeros((n_slots,) + np.shape(a), jnp.asarray(a).dtype)
            for key, a in cache0.items() if key not in self.arena})

        # host-side paging state
        self.tables = np.zeros((n_slots, self.nb_max), np.int32)
        self.lens = np.zeros(n_slots, np.int64)
        self.slot_bids: list[list[int]] = [[] for _ in range(n_slots)]
        self.cow_blk: list[int | None] = [None] * n_slots
        self.cow_spare: list[int | None] = [None] * n_slots
        self.partial_reg: list[tuple[int, int] | None] = [None] * n_slots
        self._stats: list[dict] = [{} for _ in range(n_slots)]
        # per-token arena bytes (for the bytes-saved-vs-dense telemetry)
        self._token_bytes = sum(
            a.dtype.itemsize * (int(np.prod(a.shape)) // num_blocks)
            // block_size for a in self.arena.values())
        # peak occupancy: a drained pool always reads 0 blocks in use, so
        # the memory-savings evidence is tracked at its high-water mark
        self.peak_blocks_in_use = 0
        self.peak_bytes_saved = 0

        if mesh is not None:
            self.params = self._place(params)

        def prefill(p, b):
            return engine.prefill(cfg, p, b)
        self._prefill = jax.jit(prefill)
        # the chunked-prefill fold: one step per prompt block.  jit
        # specializes per (q_offset, chunk/prefix shape) — a fixed bucket
        # set in the steady state, shared by cold and resumed folds (that
        # sharing is what makes a resume bitwise: same executable) and
        # shared *process-wide* across every adapter of this config
        # (chunk_fold_fn), so a second gateway slice pays zero retraces
        self._chunk_fn = chunk_fold_fn(cfg)
        fmt = self.arena_format
        self._gather_prefix = jit_arena(self._gather_prefix_impl, fmt, 2)
        if cfg.family == "encdec":
            self._encode = jax.jit(lambda p, e: engine.encode_cross(cfg, p, e))
        # donate the arena (and dense cache) through every call that rebinds
        # it, so the .at[].set updates alias in place instead of holding a
        # second full arena copy — the whole point of the fixed byte budget.
        # Every backend donates, the CPU included, so the CPU tests see the
        # chip's semantics: a stale reference to a donated arena is deleted.
        same = lambda f: f
        self._scatter = jit_arena(self._scatter_impl, fmt, 3, out=same,
                                  donate_argnums=(0,))
        self._copy = jit_arena(self._copy_impl, fmt, 3, out=same,
                               donate_argnums=(0,))
        self._write_block = jit_arena(self._write_block_impl, fmt, 3,
                                      out=same, donate_argnums=(0,))
        if self.inplace:
            self._decode = decode_tick(cfg, self.max_len, "pallas"
                                       if self.kernel else "xla", fmt)
        else:
            self._decode = jax.jit(self._tick_impl, donate_argnums=(1, 2))
        # the cascade tick sits NEXT TO the flat one, not instead of it:
        # a tick on which no chain is shared by >= 2 lanes degrades to
        # self._decode — the *same* executable, hence bitwise.  jit
        # specializes per metadata bucket shape (next-pow-2 padded group /
        # chain / lane / suffix counts), a fixed set in the steady state.
        if self.backend == "cascade":
            self._decode_cascade = decode_tick(cfg, self.max_len, "cascade",
                                               fmt)

    def _place(self, tree):
        """Commit ``tree`` to ``self.placement`` (left as is where None)."""
        if self.placement is None:
            return tree
        return jax.device_put(tree, self.placement)

    # -- jitted bodies ------------------------------------------------------

    def _scatter_impl(self, arena, padded, wbids):
        """Write a prompt's blocks: ``padded[key]`` is the B=1 cache padded
        to max_len; ``wbids[j]`` is the arena slot for logical block j (the
        trash block for shared/unused blocks, whose values are discarded)."""
        out = {}
        for key in self.seq_keys:
            a = padded[key]
            ax = a.ndim + engine.seq_axis(key)  # the sequence axis
            b = a.reshape(a.shape[:ax] + (self.nb_max, self.bs)
                          + a.shape[ax + 1:])
            b = jnp.moveaxis(b, ax, ax - 1)     # block axis just before B
            idx = (slice(None),) * (ax - 1) + (wbids,)
            out[key] = arena[key].at[idx].set(b)
        return out

    def _copy_impl(self, arena, dst, src):
        """Copy block ``src`` onto block ``dst`` for every key (CoW)."""
        out = {}
        for key, a in arena.items():
            ax = self._bax[key]
            idx = (slice(None),) * ax + (dst,)
            out[key] = a.at[idx].set(jnp.take(a, src, axis=ax))
        return out

    def _write_block_impl(self, arena, dst, contents):
        """Land externally-sourced block contents (cross-slice migration)
        at block id ``dst``: ``contents[key]`` is one block in the
        :meth:`arena_block` layout (the block axis squeezed out)."""
        out = dict(arena)
        for key, blk in contents.items():
            ax = self._bax[key]
            idx = (slice(None),) * ax + (dst,)
            out[key] = arena[key].at[idx].set(blk)
        return out

    def _gather_prefix_impl(self, arena, bids):
        """Gather an H-block chain into the dense prefix layout that
        :func:`engine.prefill_chunked` consumes: per sequence key,
        block axis ``bids`` -> ``(..., B, nb*bs, *post)`` (B=1 row)."""
        out = {}
        for key in self.seq_keys:
            g = jnp.take(arena[key], bids, axis=self._bax[key])
            seq = g.ndim + engine.seq_axis(key)  # (..., 1, n, bs, *post)
            g = jnp.moveaxis(g, self._bax[key], seq - 1)     # behind B
            out[key] = g.reshape(g.shape[:seq - 1]
                                 + (bids.shape[0] * self.bs,)
                                 + g.shape[seq + 1:])
        return out

    # -- the chunked-prefill fold -------------------------------------------

    def _prefix_cache(self, n_blocks: int, bids=None, state=None):
        """Prefix cache for a fold starting at block ``n_blocks``: gathered
        arena blocks (or zero-length arrays for a cold fold), the hybrid
        boundary state, and the encdec cross K/V."""
        q0 = n_blocks * self.bs
        if n_blocks:
            cache = dict(self._gather_prefix(self.arena,
                                             jnp.asarray(bids, jnp.int32)))
        else:
            empty = engine.init_cache(self.cfg, 1, 0, abstract=True)
            cache = self._place({
                key: jnp.zeros(empty[key].shape, empty[key].dtype)
                for key in self.seq_keys if key in empty})
        cache["len"] = jnp.int32(q0)
        if self.cfg.family == "hybrid":
            if state is None:
                L = self.cfg.n_layers
                state = self._place({
                    "conv": jnp.zeros((L, 1, self.cfg.conv_k - 1,
                                       self.cfg.inner), self.cfg.dtype),
                    "ssm": jnp.zeros((L, 1, self.cfg.inner,
                                      self.cfg.ssm_state), jnp.float32)})
            cache.update(state)
        if self.cfg.family == "encdec":
            batch = self.extras() if self.extras is not None else {}
            cache["xk"], cache["xv"] = self._encode(self.params,
                                                    batch["enc_embed"])
        return cache

    def _fold_prefill(self, prompt: np.ndarray, q0: int, cache,
                      keys: list[bytes]):
        """Run the chunk fold over ``prompt[q0:]``.  Returns (final cache,
        last-token logits, boundary-state snapshots to commit on success)."""
        P = len(prompt)
        n_full = P // self.bs
        snapshots: list[tuple[bytes, dict]] = []
        q, logits = q0, None
        while q < P:
            c = min(self.bs, P - q)
            batch = {"tokens": jnp.asarray(
                np.asarray(prompt[q:q + c], np.int32)[None])}
            with (self.tracer.span("prefill_chunk", q0=q, tokens=c,
                                   prefix_hit=False)
                  if self.tracer is not None else NO_SPAN):
                cache, logits = self._chunk_fn(self.params, batch, cache, q)
            q += c
            if (self.cfg.family == "hybrid" and q % self.bs == 0
                    and q // self.bs <= n_full):
                key = keys[q // self.bs - 1]
                if key not in self._boundary_states:
                    snapshots.append((key, {"conv": cache["conv"],
                                            "ssm": cache["ssm"]}))
        return cache, logits, snapshots

    def _tick_impl(self, p, arena, dense, tables, tokens, mask, wbids):
        """The gather tick (``backend="gather"``): gather -> vmapped
        decode_step -> scatter the written blocks.  Kept only as the parity
        oracle for the in-place tick, which covers every paged layout."""
        cache = dict(dense)
        for key in self.seq_keys:
            ax = self._bax[key]
            g = jnp.take(arena[key], tables, axis=ax)
            g = jnp.moveaxis(g, ax, 0)          # slot lanes leading
            g = jnp.moveaxis(g, ax + 1, ax + 2)  # block axis behind B
            seq = g.ndim + engine.seq_axis(key)
            cache[key] = g.reshape(
                g.shape[:seq - 1] + (self.nb_max * self.bs,)
                + g.shape[seq + 1:])
        new_cache, logits = jax.vmap(
            lambda c, t: engine.decode_step(self.cfg, p, c, t),
            in_axes=(0, 0))(cache, tokens[:, None])
        sel = lambda new, old: jnp.where(
            mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)
        new_dense = {key: sel(new_cache[key], dense[key]) for key in dense}
        # each slot wrote exactly one position (pre-increment len), hence
        # exactly one block; inactive lanes target the trash block.  A lane
        # whose len is out of range (at capacity, or any host-side
        # accounting drift) is routed to the trash block HERE as well as in
        # decode(): the pre-fix clamp (min(start, max_len - bs)) silently
        # aliased such lanes onto the final — possibly *shared* — block.
        oor = dense["len"] >= self.max_len
        start = jnp.where(oor, 0, (dense["len"] // self.bs) * self.bs)
        wbids = jnp.where(oor, TRASH_BLOCK, wbids)
        new_arena = {}
        for key in self.seq_keys:
            ax = self._bax[key]
            blk = jax.vmap(
                lambda a, s, ax=engine.seq_axis(key):
                jax.lax.dynamic_slice_in_dim(a, s, self.bs, axis=a.ndim + ax)
            )(new_cache[key], start)
            blk = jnp.moveaxis(blk, 0, ax)
            idx = (slice(None),) * ax + (wbids,)
            new_arena[key] = arena[key].at[idx].set(blk)
        return new_arena, new_dense, logits[:, 0]

    # -- admission ----------------------------------------------------------

    def _block_demand(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new) // self.bs)

    def validate_request(self, prompt_len: int, max_new: int) -> None:
        n_total = self._block_demand(prompt_len, max_new)
        if n_total > self.pool.capacity:
            raise ValueError(
                f"request needs {n_total} blocks worst-case; pool holds "
                f"{self.pool.capacity} (block_size={self.bs})")

    def _arming_demand(self, partial_hit: int | None) -> int:
        """Spares newly required by existing holders of a shared partial."""
        if partial_hit is None:
            return 0
        return sum(1 for s in range(self.n_slots)
                   if self.partial_reg[s]
                   and self.partial_reg[s][1] == partial_hit
                   and self.cow_spare[s] is None)

    def _admission_demand(self, prompt: np.ndarray, max_new: int) -> int:
        """Exact worst-case supply (free + evictable) an ``insert`` of this
        request consumes — asserted against the measured delta in
        tests/test_chunked_prefill.py.

        Every path: ``ceil((P + max_new)/bs)`` chain blocks minus full-
        prefix hits (referenced, not allocated), plus one unit per hit
        currently parked in the LRU (revival removes an evictable block
        without an allocation — ignoring it would overcommit exactly in the
        prefix-cache-warm steady state).

        The boundary (partial) block differs by path and must be priced
        once, not twice.  Chunked fold: the slot recomputes the boundary
        chunk into its own fresh block — already inside ``n_total - hits``
        — and never references the shared partial, so a partial hit adds
        nothing.  Legacy one-shot path: the shared partial is additionally
        held for the slot's lifetime (its LRU revival consumes supply) and
        newly-shared status obliges existing holders to take copy-on-write
        spares (``_arming_demand``).
        """
        pool = self.pool
        n_total = self._block_demand(len(prompt), max_new)
        hits, partial_hit, _, _ = pool.match_prefix(
            np.asarray(prompt, np.int32), count=False)
        revived = sum(1 for b in hits if pool.refcount[b] == 0)
        demand = n_total - len(hits) + revived
        if not self.chunked:
            if partial_hit is not None and pool.refcount[partial_hit] == 0:
                demand += 1
            demand += self._arming_demand(partial_hit)
        return demand

    def can_admit(self, prompt: np.ndarray, max_new: int) -> bool:
        """Worst-case block demand vs free + evictable supply; the batcher
        queues the request when it does not fit (never fails mid-flight)."""
        return self._admission_demand(prompt, max_new) <= \
            self.pool.available()

    # -- slot lifecycle ------------------------------------------------------

    def insert(self, slot: int, prompt: np.ndarray,
               max_new: int | None = None) -> int:
        P = len(prompt)
        if max_new is None:
            max_new = max(1, self.max_len - P)
        if P + max_new > self.max_len:
            raise ValueError(f"prompt {P} + {max_new} new tokens exceeds "
                             f"slot capacity {self.max_len}")
        prompt = np.asarray(prompt, np.int32)
        n_total = self._block_demand(P, max_new)
        n_full = P // self.bs
        hits, partial_hit, keys, pkey = self.pool.match_prefix(prompt)
        if self.chunked:
            return self._insert_chunked(slot, prompt, n_total, n_full,
                                        hits, partial_hit, keys, pkey)
        return self._insert_oneshot(slot, prompt, n_total, n_full,
                                    hits, partial_hit, keys, pkey)

    def _resume_blocks(self, P: int, hits: list[int],
                       keys: list[bytes]) -> int:
        """How many prefix blocks the fold can skip: the hit chain, capped
        so at least one prompt token remains (the fold must produce the
        last-token logits), and for hybrid capped at the deepest boundary
        whose recurrent-state snapshot is still cached."""
        H = len(hits)
        if self.cfg.family == "hybrid":
            while H > 0 and keys[H - 1] not in self._boundary_states:
                H -= 1
        while H > 0 and H * self.bs >= P:
            H -= 1
        return H

    def _insert_chunked(self, slot: int, prompt: np.ndarray, n_total: int,
                        n_full: int, hits, partial_hit, keys, pkey) -> int:
        """Chunk-fold admission: reference every full-block hit (storage
        sharing), resume the prefill fold past the deepest usable boundary
        (compute skipping), and recompute the trailing partial chunk into a
        private block — the shared partial is never referenced, so no
        copy-on-write arming and nothing to disarm on rollback."""
        P = len(prompt)
        pool = self.pool
        # take references on every hit before allocating (allocation may
        # evict from the LRU the hits are parked in); on exhaustion release
        # everything this insert took so a failed admission leaks nothing
        bids: list[int] = []
        fresh: list[tuple[int, bytes | None, int]] = []  # (blk_idx, key, bid)
        try:
            bids.extend(pool.acquire(b) for b in hits)
            for j in range(len(hits), n_full):
                b = pool.alloc()
                fresh.append((j, keys[j], b))
                bids.append(b)
            if n_full * self.bs < P:                   # partial prompt block
                b = pool.alloc()
                # register only when the chunk is not already indexed by a
                # sibling (first registration wins anyway); the block is
                # private either way — decode writes it in place
                fresh.append((n_full, None if partial_hit is not None
                              else pkey, b))
                bids.append(b)
            while len(bids) < n_total:                 # generation blocks
                bids.append(pool.alloc())
        except PoolExhausted:
            for b in bids:
                pool.release(b)
            raise

        H = self._resume_blocks(P, hits, keys)
        q0 = H * self.bs
        if H and self.tracer is not None:
            # the H prefix-hit chunks are *skipped*, not folded — mark the
            # resume point so the trace shows where compute was saved
            self.tracer.instant("prefix_resume",
                                args={"blocks": H, "tokens_skipped": q0,
                                      "prefix_hit": True})
        state = None
        if H and self.cfg.family == "hybrid":
            state = self._boundary_states[keys[H - 1]]
            self._boundary_states.move_to_end(keys[H - 1])   # LRU recency
        cache = self._prefix_cache(H, bids[:H] if H else None, state)
        cache, logits, snapshots = self._fold_prefill(prompt, q0, cache,
                                                      keys)
        cache = dict(cache)
        padded = {key: _pad_seq(cache.pop(key), self.max_len, key)
                  for key in self.seq_keys}
        wbids = np.zeros(self.nb_max, np.int32)
        for j, key, b in fresh:
            wbids[j] = b
        self.arena = self._scatter(self.arena, padded, jnp.asarray(wbids))
        # index only after the contents exist (a failed insert must never
        # leave a key pointing at an unwritten block)
        for j, key, b in fresh:
            if key is not None:
                pool.register(key, b, partial=j >= n_full)
                if j >= n_full:
                    self.partial_reg[slot] = (j, b)
        for key, st in snapshots:
            self._boundary_states.setdefault(key, st)
            self._boundary_states.move_to_end(key)
        while len(self._boundary_states) > self._max_boundary_states:
            self._boundary_states.popitem(last=False)
        for key in self.cache:
            if key == "len":
                continue
            self.cache[key] = self.cache[key].at[slot].set(cache[key])
        self.cache["len"] = self.cache["len"].at[slot].set(P)

        self.tables[slot, :] = TRASH_BLOCK
        self.tables[slot, :len(bids)] = bids
        self.lens[slot] = P
        self.slot_bids[slot] = bids
        self.prefill_tokens_total += P
        self.prefill_tokens_skipped_total += q0
        self._stats[slot] = {
            "kv_blocks": n_total,
            "prefix_hit_blocks": len(hits)
            + (1 if partial_hit is not None else 0),
            "prefill_tokens_skipped": q0}
        self._update_peaks()
        return int(jnp.argmax(logits[0]))

    def _insert_oneshot(self, slot: int, prompt: np.ndarray, n_total: int,
                        n_full: int, hits, partial_hit, keys, pkey) -> int:
        """Legacy (PR 2) path: one-shot prefill over the whole prompt —
        storage is shared (hit blocks are referenced, their recomputed
        values discarded) but no compute is skipped; a shared partial block
        is held read-only with lazy copy-on-write."""
        P = len(prompt)
        pool = self.pool
        # take references on every hit before allocating (allocation may
        # evict from the LRU the hits are parked in); on exhaustion release
        # everything this insert took — including the spares it armed other
        # holders with — so a failed admission leaks nothing
        bids = []
        fresh: list[tuple[int, bytes, int]] = []       # (blk_idx, key, bid)
        armed: list[tuple[int, tuple[int, int]]] = []  # (slot, partial_reg)
        try:
            bids.extend(pool.acquire(b) for b in hits)
            for j in range(len(hits), n_full):
                b = pool.alloc()
                fresh.append((j, keys[j], b))
                bids.append(b)
            if n_full * self.bs < P:                   # partial prompt block
                if partial_hit is not None:
                    # share it; every holder copies before its first write
                    self._arm_holders(partial_hit, armed)
                    pool.acquire(partial_hit)
                    bids.append(partial_hit)
                    self.cow_blk[slot] = n_full
                    self.cow_spare[slot] = pool.alloc()
                else:
                    b = pool.alloc()
                    fresh.append((n_full, pkey, b))
                    bids.append(b)
            while len(bids) < n_total:                 # generation blocks
                bids.append(pool.alloc())
        except PoolExhausted:
            for b in bids:
                pool.release(b)
            if self.cow_spare[slot] is not None:
                pool.release(self.cow_spare[slot])
            self.cow_blk[slot] = self.cow_spare[slot] = None
            self.partial_reg[slot] = None
            for s, prev in armed:                      # disarm: un-leak the
                pool.release(self.cow_spare[s])        # holders' spares
                self.cow_blk[s] = self.cow_spare[s] = None
                self.partial_reg[s] = prev
            raise

        # prefill and write the freshly-owned prompt blocks into the arena;
        # shared blocks keep the sibling's (bit-identical) values
        batch = {"tokens": jnp.asarray(prompt[None])}
        if self.extras is not None:
            batch.update(self.extras())
        cache1, logits = self._prefill(self.params, batch)
        cache1 = dict(cache1)
        padded = {key: _pad_seq(cache1.pop(key), self.max_len, key)
                  for key in self.seq_keys}
        wbids = np.zeros(self.nb_max, np.int32)
        for j, key, b in fresh:
            wbids[j] = b
        self.arena = self._scatter(self.arena, padded,
                                   jnp.asarray(wbids))
        # index only after the contents exist (a failed insert must never
        # leave a key pointing at an unwritten block)
        for j, key, b in fresh:
            pool.register(key, b, partial=j >= n_full)
            if j >= n_full:
                self.partial_reg[slot] = (j, b)
        for key in self.cache:
            if key == "len":
                continue
            self.cache[key] = self.cache[key].at[slot].set(cache1[key])
        self.cache["len"] = self.cache["len"].at[slot].set(P)

        self.tables[slot, :] = TRASH_BLOCK
        self.tables[slot, :len(bids)] = bids
        self.lens[slot] = P
        self.slot_bids[slot] = bids
        self.prefill_tokens_total += P
        self._stats[slot] = {
            "kv_blocks": n_total,
            "prefix_hit_blocks": len(hits)
            + (1 if partial_hit is not None else 0),
            "prefill_tokens_skipped": 0}
        self._update_peaks()
        return int(jnp.argmax(logits[0]))

    def _update_peaks(self) -> None:
        in_use = self.pool.blocks_in_use()
        live = sum(1 for b in self.slot_bids if b)
        saved = (live * self.max_len - in_use * self.bs) * self._token_bytes
        self.peak_blocks_in_use = max(self.peak_blocks_in_use, in_use)
        self.peak_bytes_saved = max(self.peak_bytes_saved, saved)

    def _arm_holders(self, bid: int,
                     armed: list[tuple[int, tuple[int, int]]]) -> None:
        """Give every live holder of a newly-shared partial block a spare.

        Each successfully armed holder is appended to ``armed`` (with its
        prior ``partial_reg`` entry) *before* the next allocation can
        raise, so the caller's rollback can disarm exactly the holders this
        insert armed — spares must not leak on a failed admission."""
        for s in range(self.n_slots):
            if (self.partial_reg[s] and self.partial_reg[s][1] == bid
                    and self.cow_spare[s] is None):
                prev = self.partial_reg[s]
                spare = self.pool.alloc()
                self.cow_blk[s] = prev[0]
                self.cow_spare[s] = spare
                self.partial_reg[s] = None
                armed.append((s, prev))

    def clear(self, slot: int) -> None:
        for bid in self.slot_bids[slot]:
            self.pool.release(bid)
        if self.cow_spare[slot] is not None:
            self.pool.release(self.cow_spare[slot])
        self.cow_blk[slot] = self.cow_spare[slot] = None
        self.partial_reg[slot] = None
        self.slot_bids[slot] = []
        self.tables[slot, :] = TRASH_BLOCK
        self.lens[slot] = 0
        self.cache["len"] = self.cache["len"].at[slot].set(0)

    # -- decode --------------------------------------------------------------

    def at_capacity(self, slot: int) -> bool:
        """A slot whose context has filled every block cannot take another
        token: its next write has no block to land in.  The batcher
        discovers this hook and retires such a request as finished."""
        return bool(self.slot_bids[slot]) and \
            int(self.lens[slot]) >= self.max_len

    # -- cascade grouping (backend="cascade") --------------------------------

    def _cascade_plan(self, lanes):
        """Shared-chain groups over the given lanes (host side).

        Feeds :meth:`BlockPool.shared_chains` each lane's *full* blocks
        only (the partially-filled tail is trimmed — only positions every
        sharer holds identically may enter a group pass) plus a skip set
        of blocks armed for copy-on-write, so a group never reads a block
        another lane is about to rewrite; the pool additionally excludes
        partial, unshared, and protected-for-handoff blocks.
        """
        skip = set()
        for s in range(self.n_slots):
            if self.cow_blk[s] is not None:
                skip.add(int(self.tables[s, self.cow_blk[s]]))
            if self.cow_spare[s] is not None:
                skip.add(int(self.cow_spare[s]))
        chains = {int(s): [int(b) for b in
                           self.tables[s, :int(self.lens[s]) // self.bs]]
                  for s in lanes}
        return self.pool.shared_chains(chains, skip=skip)

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 if n <= 1 else 1 << (n - 1).bit_length()

    def _cascade_meta(self, groups) -> dict:
        """Device metadata for :func:`nn.attention.attend_decode_cascade`,
        padded to next-pow-2 bucket shapes so steady-state ticks reuse a
        fixed jit bucket set (the no-recompile pin in test_cascade.py)."""
        G = self._pow2(len(groups))
        npre = self._pow2(max(len(c) for c, _ in groups))
        lc = self._pow2(max(len(ls) for _, ls in groups))
        gt = np.full((G, npre), TRASH_BLOCK, np.int32)
        gl = np.zeros(G, np.int32)
        lanes = np.zeros((G, lc), np.int32)
        gmask = np.zeros((G, lc), bool)
        q0 = np.zeros(self.n_slots, np.int32)
        q0b = np.zeros(self.n_slots, np.int64)
        for g, (chain, ls) in enumerate(groups):
            gt[g, :len(chain)] = chain
            gl[g] = len(chain) * self.bs
            lanes[g, :len(ls)] = ls
            gmask[g, :len(ls)] = True
            for s in ls:
                q0[s] = len(chain) * self.bs
                q0b[s] = len(chain)
        # suffix tables must cover [q0 blocks, blocks holding cache_len)
        # for every lane — an ungrouped lane's suffix is its whole chain
        need = [max(1, -(-(int(self.lens[s]) + 1) // self.bs) - int(q0b[s]))
                for s in range(self.n_slots)]
        nsuf = self._pow2(max(need))
        st = np.full((self.n_slots, nsuf), TRASH_BLOCK, np.int32)
        for s in range(self.n_slots):
            row = self.tables[s, int(q0b[s]):int(q0b[s]) + nsuf]
            st[s, :len(row)] = row
        return {"group_tables": jnp.asarray(gt),
                "group_len": jnp.asarray(gl),
                "group_lanes": jnp.asarray(lanes),
                "group_mask": jnp.asarray(gmask),
                "lane_q0": jnp.asarray(q0),
                "suffix_tables": jnp.asarray(st)}

    def cascade_stats(self) -> dict:
        """Host-side grouping snapshot over the current live lanes
        (benchmarks/kvcache_bench.py --cascade): the groups the next tick
        would form, and the per-layer prefix rows attended once per
        *group* vs once per *lane* — the O(prefix) vs O(lanes x prefix)
        traffic claim the BENCH_cascade gate checks."""
        lanes = [s for s in range(self.n_slots)
                 if self.slot_bids[s] and not self.at_capacity(s)]
        groups = self._cascade_plan(lanes)
        shapes = [(len(c), len(ls)) for c, ls in groups]
        return {
            "groups": len(groups),
            "grouped_lanes": sum(n for _, n in shapes),
            "prefix_rows": sum(c * self.bs for c, _ in shapes),
            "prefix_rows_flat": sum(c * self.bs * n for c, n in shapes),
        }

    def decode(self, tokens: np.ndarray, active: np.ndarray) -> np.ndarray:
        """One batched tick.  With a tracer attached its host phases are
        spans: ``tick.prepare`` (write targets, copy-on-write, the cascade
        plan, the host-to-device arguments), ``tick.dispatch`` (the call of
        the jitted tick) and ``tick.sample`` (argmax and its readback,
        where the host waits for the device)."""
        tr = self.tracer
        with (tr.span("tick.prepare") if tr is not None
              else NO_SPAN) as sp:
            active, wbids, meta = self._write_targets(active)
            args = (jnp.asarray(self.tables),
                    jnp.asarray(tokens, jnp.int32)[:, None],
                    jnp.asarray(active, bool), jnp.asarray(wbids))
            work = {}
            if self.cfg.moe is not None:
                work.update(engine.decode_moe_work(
                    self.cfg, self.n_slots, int(active.sum())))
            if self.kernel and not self.cfg.mla:
                work.update(engine.decode_attn_work(
                    self.cfg, self.lens, self.bs, self.nb_max))
            for key, count in work.items():
                name = f"{key}_total"
                setattr(self, name, getattr(self, name) + count)
            self.last_work = work
            if sp is not None:
                sp.set(active=int(active.sum()),
                       live_tokens=int(self.lens[active].sum()), **work)
        with (tr.span("tick.dispatch") if tr is not None else NO_SPAN):
            if meta is None:
                # no chain shared by >= 2 lanes: degrade to the flat
                # in-place tick — the *same* jitted executable, hence
                # bitwise-equal
                self.arena, self.cache, logits = self._decode(
                    self.params, self.arena, self.cache, *args)
            else:
                self.arena, self.cache, logits = self._decode_cascade(
                    self.params, self.arena, self.cache, *args, meta)
        self.lens[active] += 1
        self.last_logits = logits           # (n_slots, vocab) — parity tests
        with (tr.span("tick.sample") if tr is not None else NO_SPAN):
            return np.asarray(jnp.argmax(logits, -1))

    def _write_targets(self, active: np.ndarray):
        """The tick's host-side plan: the lanes that write, each lane's
        arena write block (after copy-on-write of a shared block) and,
        under cascade, the shared-chain metadata.  Returns (active,
        wbids, meta)."""
        active = np.asarray(active, bool).copy()
        wbids = np.full(self.n_slots, TRASH_BLOCK, np.int32)
        for slot in np.nonzero(active)[0]:
            if self.at_capacity(slot):
                # a full slot must not scatter: len // bs indexes past the
                # table and the pre-fix clamp silently overwrote the final
                # block — which may be a *shared* prefix block.  Route the
                # lane to the trash block and keep its state frozen.
                active[slot] = False
                continue
            blk = int(self.lens[slot]) // self.bs
            bid = int(self.tables[slot, blk])
            if self.cow_blk[slot] is not None and blk == self.cow_blk[slot]:
                spare = self.cow_spare[slot]
                self.arena = self._copy(self.arena, spare, bid)
                self.pool.cow_copies += 1
                self.pool.release(bid)
                self.tables[slot, blk] = spare
                self.slot_bids[slot][blk] = spare
                self.cow_blk[slot] = self.cow_spare[slot] = None
                bid = spare
            elif self.pool.is_partial(bid):
                # sole owner writes in place: the cached chunk changes, so
                # the index entry must go before the write lands
                self.pool.drop_partial(bid)
                self.partial_reg[slot] = None
            wbids[slot] = bid
        meta = None
        if self.backend == "cascade":
            # grouping runs AFTER the CoW/write-target loop above so a
            # block resolved this tick can never be both read by a group
            # pass and rewritten by its owner
            groups = self._cascade_plan(np.nonzero(active)[0])
            self.last_groups = len(groups)
            if groups:
                meta = self._cascade_meta(groups)
        return active, wbids, meta

    # -- telemetry -----------------------------------------------------------

    def arena_block(self, key: str, bid: int):
        """One arena block's contents for ``key``: the B=1 cache slice of
        ``block_size`` positions (layout-agnostic accessor for tests)."""
        return jnp.take(self.arena[key], bid, axis=self._bax[key])

    def tick_bytes_proxy(self) -> dict:
        """Analytic arena bytes one decode tick moves under each dataflow.

        A model of the traffic each tick's *dataflow* implies (what the
        TPU kernel's per-block DMA would actually stream), not a measured
        counter — benchmarks/kvcache_bench.py reports it alongside wall
        time.  The gather tick reads every lane's full ``nb_max`` chain,
        materializes + rewrites the dense per-slot cache, and scatters one
        block back; the in-place tick reads only the blocks live chains
        own and writes a single row per lane.
        """
        token = self._token_bytes
        n, ml, bs = self.n_slots, self.max_len, self.bs
        gather = n * ml * token * 2 + n * bs * token
        live_rows = sum(-(-(int(ln) + 1) // bs) * bs
                        for ln, b in zip(self.lens, self.slot_bids) if b)
        inplace = live_rows * token + n * token
        # cascade: each shared chain's prefix rows stream once per *group*
        # instead of once per lane; suffixes stream per lane as before
        groups = self._cascade_plan(
            [s for s in range(n) if self.slot_bids[s]])
        q0b = {s: len(c) for c, ls in groups for s in ls}
        prefix_rows = sum(len(c) * bs for c, _ in groups)
        suffix_rows = sum((-(-(int(ln) + 1) // bs) - q0b.get(s, 0)) * bs
                          for s, (ln, b) in
                          enumerate(zip(self.lens, self.slot_bids)) if b)
        cascade = (prefix_rows + suffix_rows) * token + n * token
        return {"gather": gather, "inplace": inplace, "cascade": cascade}

    def slot_stats(self, slot: int) -> dict:
        return dict(self._stats[slot])

    def jit_fns(self) -> dict[str, object]:
        """Named jitted entry points, for obs.RecompileDetector.track.
        The chunk fold is process-wide (shared across adapters of one
        config), so its bucket count reflects every adapter's folds."""
        fns = {"prefill": self._prefill, "chunk_fold": self._chunk_fn,
               "gather_prefix": self._gather_prefix,
               "scatter": self._scatter, "copy": self._copy,
               "write_block": self._write_block, "decode": self._decode}
        if self.backend == "cascade":
            fns["decode_cascade"] = self._decode_cascade
            # the cascade tick's inner executables are module-level jits in
            # kernels/paged_attn.py (process-wide, like chunk_fold): the
            # grouped-prefix pass, the per-lane suffix pass, and the
            # softmax-state merge.  Tracking them separately catches a leak
            # the outer decode_cascade bucket count can hide — a pow2
            # cascade-meta bucket crossing recompiles all three
            from repro.kernels import paged_attn as pk
            fns["cascade_prefix"] = pk.cascade_prefix_attention
            fns["cascade_suffix"] = pk.paged_decode_attention_with_state
            fns["cascade_merge"] = pk.merge_attn_states
        if self.cfg.family == "encdec":
            fns["encode"] = self._encode
        return fns

    def cost_args(self) -> dict[str, tuple]:
        """The serving-relevant stages of :meth:`jit_fns` paired with
        representative steady-state arguments, for obs.costmodel roofline
        attribution (``fn.lower(*args)`` — shapes only, nothing executes):
        the in-place decode tick against the live arena, one cold
        chunk-fold step (the block-size bucket every fold passes through),
        a one-block prefill, and the CoW/migration block copy."""
        batch = {"tokens": jnp.zeros((1, self.bs), jnp.int32)}
        if self.extras is not None:
            batch.update(self.extras())
        tokens = jnp.zeros((self.n_slots, 1), jnp.int32)
        mask = jnp.ones((self.n_slots,), bool)
        wbids = jnp.zeros((self.n_slots,), jnp.int32)
        return {
            "prefill": (self._prefill, (self.params, batch)),
            "chunk_fold": (self._chunk_fn,
                           (self.params, batch, self._prefix_cache(0), 0)),
            "decode": (self._decode,
                       (self.params, self.arena, self.cache,
                        jnp.asarray(self.tables), tokens, mask, wbids)),
            "copy": (self._copy, (self.arena, jnp.int32(0), jnp.int32(1))),
        }

    def pool_stats(self) -> dict:
        st = self.pool.stats()
        live = sum(1 for b in self.slot_bids if b)
        st["bytes_dense_equiv"] = live * self.max_len * self._token_bytes
        st["bytes_paged"] = st["blocks_in_use"] * self.bs * self._token_bytes
        st["bytes_saved_vs_dense"] = (st["bytes_dense_equiv"]
                                      - st["bytes_paged"])
        st["peak_blocks_in_use"] = self.peak_blocks_in_use
        st["peak_bytes_saved_vs_dense"] = self.peak_bytes_saved
        st["prefill_tokens_total"] = self.prefill_tokens_total
        st["prefill_tokens_skipped"] = self.prefill_tokens_skipped_total
        st["boundary_state_bytes"] = sum(
            a.nbytes for state in self._boundary_states.values()
            for a in state.values())
        return st
