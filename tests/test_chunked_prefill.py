"""Prefix-hit chunked prefill: the block-size prefill fold, bitwise resume
parity for all four attention families (engine + adapter + arena blocks),
no-recompile steady state, exact admission pricing, the PoolExhausted
rollback disarm, and the at-capacity trash-block routing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.serve import engine
from repro.serve.gateway.slots import (ContinuousBatcher, Request,
                                       make_adapter)
from repro.serve.kvcache import PoolExhausted, TRASH_BLOCK

FAMILY_ARCH = {
    "decoder": "stablelm_3b",
    "moe": "deepseek_moe_16b",
    "hybrid": "hymba_1_5b",
    "encdec": "whisper_medium",
    "mla": "moonshot_v1_16b_a3b",
}

BS = 4
SEQ = ("k", "v", "latent")       # the attention cache keys a fold extends


def _setup(arch, seed=0):
    cfg = dataclasses.replace(configs.smoke_config(arch),
                              param_dtype="float32")
    params, _ = lm.init(jax.random.key(0), cfg, {})
    extras = None
    if cfg.family == "encdec":
        rng = np.random.default_rng(99)
        enc = jnp.asarray(rng.normal(0, 1, (1, cfg.enc_len, cfg.d_model)),
                          jnp.float32)
        extras = lambda: {"enc_embed": enc}
    return cfg, params, extras


# ==========================================================================
# Engine-level fold parity (tentpole acceptance: all four families).
# ==========================================================================

def _empty_prefix(cfg, params, extras):
    empty = engine.init_cache(cfg, 1, 0, abstract=True)
    cache = {key: jnp.zeros(empty[key].shape, empty[key].dtype)
             for key in SEQ if key in empty}
    cache["len"] = jnp.int32(0)
    if cfg.family == "hybrid":
        cache["conv"] = jnp.zeros((cfg.n_layers, 1, cfg.conv_k - 1,
                                   cfg.inner), cfg.dtype)
        cache["ssm"] = jnp.zeros((cfg.n_layers, 1, cfg.inner,
                                  cfg.ssm_state), jnp.float32)
    if cfg.family == "encdec":
        cache["xk"], cache["xv"] = engine.encode_cross(
            cfg, params, extras()["enc_embed"])
    return cache


def _fold(cfg, params, prompt, cache, start):
    q, logits = start, None
    while q < len(prompt):
        c = min(BS, len(prompt) - q)
        cache, logits = engine.prefill_chunked(
            cfg, params, {"tokens": jnp.asarray(prompt[None, q:q + c])},
            cache, q)
        q += c
    return cache, logits


@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_engine_fold_resume_bitwise(family):
    """Resuming the prefill fold at an H-block prefix must reproduce the
    cold fold's logits and K/V bit-for-bit (assert_array_equal): chunk j's
    compiled graph is shape-identical in both folds.  H=0 degenerates to
    the cold fold itself."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(1)
    P = 11                                       # 2 full blocks + partial
    prompt = rng.integers(0, cfg.vocab, size=P, dtype=np.int32)
    cold_cache, cold_logits = _fold(cfg, params, prompt,
                                    _empty_prefix(cfg, params, extras), 0)
    for H in (0, 1, 2):
        q0 = H * BS
        warm = {"len": jnp.int32(q0)}
        for key in SEQ:
            if key in cold_cache:
                a = cold_cache[key]
                warm[key] = jax.lax.slice_in_dim(
                    a, 0, q0, axis=a.ndim + engine.seq_axis(key))
        if family == "hybrid":
            # the recurrent boundary state comes from folding the prefix —
            # exactly what the adapter snapshots during a cold admission
            pc, _ = _fold(cfg, params, prompt[:q0],
                          _empty_prefix(cfg, params, extras), 0)
            warm["conv"], warm["ssm"] = pc["conv"], pc["ssm"]
        if family == "encdec":
            warm["xk"], warm["xv"] = engine.encode_cross(
                cfg, params, extras()["enc_embed"])
        warm_cache, warm_logits = _fold(cfg, params, prompt, warm, q0)
        np.testing.assert_array_equal(np.asarray(cold_logits),
                                      np.asarray(warm_logits), err_msg=family)
        for key in SEQ:
            if key in cold_cache:
                np.testing.assert_array_equal(np.asarray(cold_cache[key]),
                                              np.asarray(warm_cache[key]),
                                              err_msg=(family, key, H))


def test_engine_fold_resume_bitwise_sliced_window():
    """When the window is smaller than the prefix, windowed layers attend
    only the trailing ``window`` gathered keys (the O(S·window) bound).
    The slice must preserve both the fold's bitwise resume property and
    agreement with the one-shot prefill's sliding-window math."""
    cfg = dataclasses.replace(configs.smoke_config("hymba_1_5b"),
                              param_dtype="float32", window=2)
    params, _ = lm.init(jax.random.key(0), cfg, {})
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, size=11, dtype=np.int32)
    cold_cache, cold_logits = _fold(cfg, params, prompt,
                                    _empty_prefix(cfg, params, None), 0)
    pc, _ = _fold(cfg, params, prompt[:2 * BS],
                  _empty_prefix(cfg, params, None), 0)
    warm = {"len": jnp.int32(2 * BS),
            "k": cold_cache["k"][..., :2 * BS, :, :],
            "v": cold_cache["v"][..., :2 * BS, :, :],
            "conv": pc["conv"], "ssm": pc["ssm"]}
    warm_cache, warm_logits = _fold(cfg, params, prompt, warm, 2 * BS)
    np.testing.assert_array_equal(np.asarray(cold_logits),
                                  np.asarray(warm_logits))
    np.testing.assert_array_equal(np.asarray(cold_cache["k"]),
                                  np.asarray(warm_cache["k"]))
    # and the slice is semantically exact: the fold agrees with the
    # one-shot attend_sliding prefill up to graph-shape ulps
    _, oneshot_logits = engine.prefill(cfg, params,
                                       {"tokens": jnp.asarray(prompt[None])})
    np.testing.assert_allclose(np.asarray(cold_logits),
                               np.asarray(oneshot_logits),
                               rtol=1e-4, atol=1e-4)


# ==========================================================================
# Adapter-level parity: warm insert == cold insert, blocks and logits.
# ==========================================================================

def _slot_blocks(ad, slot):
    """Arena contents of a slot's chain, keyed (key, logical block idx)."""
    out = {}
    for j, bid in enumerate(ad.slot_bids[slot]):
        for key in ad.seq_keys:
            out[key, j] = np.asarray(ad.arena_block(key, bid))
    return out


@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_adapter_resume_matches_cold_insert(family):
    """A prefix-hit insert must scatter bit-identical arena blocks and pick
    the same next token as the identical prompt admitted cold — including a
    shared prefix that ends mid-block (partial hit) — while actually
    skipping the shared blocks' prefill."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(2)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS, dtype=np.int32)
    tail_a = rng.integers(0, cfg.vocab, size=3, dtype=np.int32)
    tail_b = rng.integers(0, cfg.vocab, size=3, dtype=np.int32)
    pa = np.concatenate([prefix, tail_a])
    pb = np.concatenate([prefix, tail_b])

    cold = make_adapter(cfg, params, n_slots=2, max_len=32, extras=extras,
                        paged=True, block_size=BS)
    tok_cold = cold.insert(0, pb, max_new=4)
    blocks_cold = _slot_blocks(cold, 0)

    warm = make_adapter(cfg, params, n_slots=2, max_len=32, extras=extras,
                        paged=True, block_size=BS)
    warm.insert(0, pa, max_new=4)                # seeds the radix prefix
    tok_warm = warm.insert(1, pb, max_new=4)
    assert warm.slot_stats(1)["prefill_tokens_skipped"] == 2 * BS
    assert warm.slot_stats(1)["prefix_hit_blocks"] == 2
    assert tok_warm == tok_cold, family
    blocks_warm = _slot_blocks(warm, 1)
    assert blocks_cold.keys() == blocks_warm.keys()
    for where, a in blocks_cold.items():
        np.testing.assert_array_equal(a, blocks_warm[where],
                                      err_msg=(family,) + where)

    # a hit that ends mid-block: identical prompt, full chain + partial hit;
    # the fold recomputes the boundary chunk into a private block
    warm.clear(1)
    tok_mid = warm.insert(1, pa, max_new=4)
    st = warm.slot_stats(1)
    assert st["prefix_hit_blocks"] == 3          # 2 full + the partial
    assert st["prefill_tokens_skipped"] == 2 * BS
    oracle = make_adapter(cfg, params, n_slots=1, max_len=32, extras=extras,
                          paged=True, block_size=BS)
    assert tok_mid == oracle.insert(0, pa, max_new=4)
    mid_blocks = _slot_blocks(warm, 1)
    for where, a in _slot_blocks(oracle, 0).items():
        np.testing.assert_array_equal(a, mid_blocks[where],
                                      err_msg=(family,) + where)


def test_adapter_divergent_writers_stay_isolated():
    """Chunked-path replacement for lazy copy-on-write: two slots admitted
    from the same prompt (full-coverage partial hit) decode into *private*
    boundary blocks, so one slot's divergent writes must leave the sibling's
    blocks and logits untouched, bit for bit."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, size=6, dtype=np.int32)

    def mk():
        ad = make_adapter(cfg, params, n_slots=2, max_len=32,
                          paged=True, block_size=BS)
        ad.insert(0, prompt, max_new=8)
        ad.insert(1, prompt, max_new=8)
        return ad

    a, b = mk(), mk()
    blocks0 = _slot_blocks(a, 0)
    # slot 1 diverges for four steps; slot 0 idle
    for tok in (3, 11, 5, 1):
        a.decode(np.asarray([0, tok], np.int32),
                 np.asarray([False, True]))
    for where, arr in blocks0.items():           # sibling blocks untouched
        np.testing.assert_array_equal(arr, _slot_blocks(a, 0)[where])
    # slot 0 now decodes exactly as if slot 1 had never moved (oracle b)
    for tok in (7, 2, 5, 9):
        a.decode(np.asarray([tok, 0], np.int32),
                 np.asarray([True, False]))
        b.decode(np.asarray([tok, 0], np.int32),
                 np.asarray([True, False]))
        np.testing.assert_array_equal(np.asarray(a.last_logits[0]),
                                      np.asarray(b.last_logits[0]))


# ==========================================================================
# Recompile-free steady state.
# ==========================================================================

def test_fold_steady_state_never_recompiles():
    """Once a (prefix blocks, chunk shape) bucket is compiled, further
    inserts of the same shape — cold or resumed — reuse it."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(4)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS, dtype=np.int32)
    ad = make_adapter(cfg, params, n_slots=2, max_len=32,
                      paged=True, block_size=BS)
    mk = lambda: np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=3, dtype=np.int32)])
    ad.insert(0, mk(), max_new=4)                # cold: compiles the fold
    ad.insert(1, mk(), max_new=4)                # warm: compiles the resume
    n_chunk = ad._chunk_fn._cache_size()
    n_gather = ad._gather_prefix._cache_size()
    ad.clear(1)
    for _ in range(3):                           # same-bucket warm inserts
        ad.insert(1, mk(), max_new=4)
        ad.clear(1)
    assert ad._chunk_fn._cache_size() == n_chunk
    assert ad._gather_prefix._cache_size() == n_gather


def test_fold_buckets_shared_process_wide():
    """The chunk fold's jit buckets are keyed by config, not by adapter
    instance: a second adapter of the same config shares the first one's
    executables and its steady-state admissions compile nothing new."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(14)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS, dtype=np.int32)
    mk = lambda: np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=3, dtype=np.int32)])
    ad1 = make_adapter(cfg, params, n_slots=2, max_len=32,
                       paged=True, block_size=BS)
    ad2 = make_adapter(cfg, params, n_slots=2, max_len=32,
                       paged=True, block_size=BS)
    assert ad1._chunk_fn is ad2._chunk_fn        # one cache per config
    ad1.insert(0, mk(), max_new=4)               # cold fold buckets
    ad1.insert(1, mk(), max_new=4)               # resume bucket
    n_chunk = ad1._chunk_fn._cache_size()
    # the second adapter admits the same shapes (its own pool starts cold,
    # so this is a cold fold + a resumed fold there) — zero new buckets
    ad2.insert(0, mk(), max_new=4)
    ad2.insert(1, mk(), max_new=4)
    assert ad2._chunk_fn._cache_size() == n_chunk
    # a *different* config gets its own fold cache, not a collision
    cfg2 = dataclasses.replace(cfg, q_chunk=max(cfg.q_chunk // 2, 1))
    params2, _ = lm.init(jax.random.key(1), cfg2, {})
    ad3 = make_adapter(cfg2, params2, n_slots=1, max_len=32,
                       paged=True, block_size=BS)
    assert ad3._chunk_fn is not ad1._chunk_fn


# ==========================================================================
# Admission pricing is exact (satellite: hit-aware demand).
# ==========================================================================

def _consumed(ad, prompt, max_new, slot):
    before = ad.pool.available()
    ad.insert(slot, prompt, max_new=max_new)
    return before - ad.pool.available()


def test_admission_demand_matches_actual_allocations():
    """``_admission_demand`` must equal the supply insert() actually
    consumes — cold, warm with a live holder (the mid-block boundary block
    must be priced once, not double-counted via arming/revival the chunked
    fold never performs), and warm from the LRU."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(5)
    p = np.concatenate([rng.integers(0, cfg.vocab, size=2 * BS,
                                     dtype=np.int32),
                        rng.integers(0, cfg.vocab, size=2, dtype=np.int32)])
    ad = make_adapter(cfg, params, n_slots=3, max_len=16,
                      paged=True, block_size=BS, num_blocks=32)
    # cold: whole chain allocated
    d = ad._admission_demand(p, 4)
    assert d == 4 and _consumed(ad, p, 4, 0) == d
    # warm, holder live: 2 full hits referenced; the boundary block is the
    # slot's own fresh block — demand is 2, not 3 (no arming, no revival)
    d = ad._admission_demand(p, 4)
    assert d == 2 and _consumed(ad, p, 4, 1) == d
    # warm from the LRU: revivals consume evictable supply 1-for-1
    ad.clear(0)
    ad.clear(1)
    d = ad._admission_demand(p, 4)
    assert d == 4                                # 4-2 hits + 2 revivals
    assert _consumed(ad, p, 4, 2) == d


def test_admission_demand_matches_legacy_path():
    """The legacy one-shot path holds the shared partial and arms existing
    holders — its demand includes exactly those units."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(6)
    p = np.concatenate([rng.integers(0, cfg.vocab, size=BS, dtype=np.int32),
                        rng.integers(0, cfg.vocab, size=2, dtype=np.int32)])
    ad = make_adapter(cfg, params, n_slots=2, max_len=16,
                      paged=True, block_size=BS, num_blocks=32,
                      chunked=False)
    assert ad._admission_demand(p, 4) == 3 == _consumed(ad, p, 4, 0)
    # holder live + unarmed: the hit block is referenced (free), and the
    # shared partial costs one arming spare + this slot's own spare + one
    # generation block = 3
    d = ad._admission_demand(p, 4)
    assert d == 3 == _consumed(ad, p, 4, 1)
    assert ad.cow_spare[0] is not None           # holder armed


# ==========================================================================
# PoolExhausted rollback disarms armed holders (satellite bugfix).
# ==========================================================================

def test_failed_insert_disarms_armed_holders():
    """A failed legacy admission must release the copy-on-write spares it
    armed sibling holders with and restore their partial registrations —
    one leaked spare per failed retry would bleed the pool dry."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(7)
    p = np.concatenate([rng.integers(0, cfg.vocab, size=BS, dtype=np.int32),
                        rng.integers(0, cfg.vocab, size=2, dtype=np.int32)])
    ad = make_adapter(cfg, params, n_slots=2, max_len=20,
                      paged=True, block_size=BS, num_blocks=7,
                      chunked=False)
    ad.insert(0, p, max_new=2)                   # full + partial: 2 blocks
    reg_before = ad.partial_reg[0]
    assert reg_before is not None and ad.cow_spare[0] is None
    avail = ad.pool.available()
    assert not ad.can_admit(p, 12)               # worst case cannot fit
    with pytest.raises(PoolExhausted):
        ad.insert(1, p, max_new=12)
    # the holder is disarmed: spare released, registration restored
    assert ad.cow_spare[0] is None and ad.cow_blk[0] is None
    assert ad.partial_reg[0] == reg_before
    assert ad.pool.available() == avail
    assert ad.pool.blocks_in_use() == 2
    # and the request still completes once supply frees up
    ad.clear(0)
    ad.insert(1, p, max_new=12)


# ==========================================================================
# At-capacity slots route to the trash block (satellite bugfix).
# ==========================================================================

def test_at_capacity_slot_writes_trash_and_finishes():
    """A slot whose len reached max_len must not scatter into its final
    block (which may be a *shared* prefix block): the lane is masked to the
    trash block, its state freezes, and the batcher retires the request."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(8)
    p = rng.integers(0, cfg.vocab, size=6, dtype=np.int32)
    ad = make_adapter(cfg, params, n_slots=1, max_len=8,
                      paged=True, block_size=BS)
    ad.insert(0, p, max_new=2)
    # force the out-of-contract state the pre-fix clamp silently corrupted
    ad.lens[0] = ad.max_len
    ad.cache["len"] = ad.cache["len"].at[0].set(ad.max_len)
    assert ad.at_capacity(0)
    final_bid = int(ad.tables[0, ad.nb_max - 1])
    before = {key: np.asarray(ad.arena_block(key, final_bid))
              for key in ad.seq_keys}
    ad.decode(np.asarray([3], np.int32), np.asarray([True]))
    assert ad.lens[0] == ad.max_len              # state frozen, no advance
    for key in ad.seq_keys:                      # final block untouched
        np.testing.assert_array_equal(
            before[key], np.asarray(ad.arena_block(key, final_bid)))

    # batcher integration: the request is surfaced as finished
    ad2 = make_adapter(cfg, params, n_slots=1, max_len=8,
                       paged=True, block_size=BS)
    batcher = ContinuousBatcher(ad2)
    batcher.submit(Request(uid=0, prompt=p[:4], max_new_tokens=4))
    batcher.step()                               # insert + 1 decode tick
    assert batcher.active[0] is not None
    ad2.lens[0] = ad2.max_len
    done = batcher.step()
    assert [r.uid for r in done] == [0]
    assert batcher.active[0] is None and not batcher.busy
