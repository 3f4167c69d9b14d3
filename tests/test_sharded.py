"""Sharded paged serving (serve/shard/): slice-placement bitwise parity,
cross-slice migration mid-decode, prefix-affinity routing (including a hit
routed to a non-owning slice), and the aggregate-concurrency acceptance bar
on a forced multi-device CPU mesh.

Single-device runs exercise everything but true multi-device placement
(slices then share the one device — the policy layer is device-agnostic);
the ``sharded`` CI job re-runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so every slice owns
a real (virtual) device and the @multi tests activate.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.dist.sharding import mesh_shape_dict, slice_meshes
from repro.launch.mesh import make_serving_mesh
from repro.models import lm
from repro.serve import engine
from repro.serve.gateway.sensors import Arrival
from repro.serve.gateway.slots import ContinuousBatcher, Request, make_adapter
from repro.serve.shard import (ShardedPromptGateway, build_slices,
                               migrate_slot)

FAMILY_ARCH = {                      # one arch per attention family
    "decoder": "stablelm_3b",
    "moe": "deepseek_moe_16b",
    "hybrid": "hymba_1_5b",
    "encdec": "whisper_medium",
    "mla": "moonshot_v1_16b_a3b",
}
BS = 4

multi = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

_SETUP_CACHE: dict = {}


def _setup(arch):
    if arch not in _SETUP_CACHE:
        cfg = dataclasses.replace(configs.smoke_config(arch),
                                  param_dtype="float32")
        params, _ = lm.init(jax.random.key(0), cfg, {})
        extras = None
        if cfg.family == "encdec":
            rng = np.random.default_rng(99)
            enc = jnp.asarray(rng.normal(0, 1, (1, cfg.enc_len, cfg.d_model)),
                              jnp.float32)
            extras = (lambda e=enc: {"enc_embed": e})
        _SETUP_CACHE[arch] = (cfg, params, extras)
    return _SETUP_CACHE[arch]


def _slice_mesh(i: int) -> Mesh:
    """Single-device slice mesh i (devices reused when there are fewer)."""
    devs = jax.devices()
    return Mesh(np.asarray([devs[i % len(devs)]]), ("model",))


def _chain_blocks(ad, slot):
    return {(key, j): np.asarray(ad.arena_block(key, bid))
            for j, bid in enumerate(ad.slot_bids[slot])
            for key in ad.seq_keys}


# ==========================================================================
# Tentpole acceptance: a sharded (mesh-placed) slice runs the unsharded
# tick bit for bit — per family, on whatever device the slice owns.
# ==========================================================================

@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_slice_placement_bitwise(family):
    """The same adapter committed to a 1-slice mesh (the *last* device, so
    the 8-device CI job really crosses devices) must reproduce the
    unsharded adapter's logits, arena blocks, and slot state bitwise."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 9)]
    un = make_adapter(cfg, params, n_slots=2, max_len=24, extras=extras,
                      paged=True, block_size=BS)
    sh = make_adapter(cfg, params, n_slots=2, max_len=24, extras=extras,
                      paged=True, block_size=BS,
                      mesh=_slice_mesh(jax.device_count() - 1))
    assert sh.mesh is not None
    for slot, p in enumerate(prompts):
        assert un.insert(slot, p, max_new=8) == sh.insert(slot, p, max_new=8)
    active = np.asarray([True, True])
    for step in range(4):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        np.testing.assert_array_equal(un.decode(forced, active),
                                      sh.decode(forced, active))
        np.testing.assert_array_equal(np.asarray(un.last_logits),
                                      np.asarray(sh.last_logits))
    assert un.slot_bids == sh.slot_bids
    for slot in range(2):
        a, b = _chain_blocks(un, slot), _chain_blocks(sh, slot)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=str(key))
    for key in un.cache:
        np.testing.assert_array_equal(np.asarray(un.cache[key]),
                                      np.asarray(sh.cache[key]))


def test_arena_specs_match_layout():
    """engine.arena_specs must produce one spec per paged key with the
    arena's exact rank, for every family layout (incl. vlm's grouped axes
    the int8 quant scales and the latent arena), and shard KV heads over
    "model" exactly when cache_specs would."""
    ms = {"data": 2, "model": 2}
    for arch, quant in [("stablelm_3b", False), ("stablelm_3b", True),
                        ("hymba_1_5b", False), ("whisper_medium", False),
                        ("llama32_vision_90b", False),
                        ("moonshot_v1_16b_a3b", False)]:
        cfg = configs.smoke_config(arch)
        if quant:
            cfg = dataclasses.replace(cfg, kv_quant=True)
        arena = engine.init_paged_arena(cfg, 4, BS, abstract=True)
        specs = engine.arena_specs(cfg, ms)
        assert set(specs) == set(arena), arch
        for key, a in arena.items():
            sp = tuple(specs[key])
            assert len(sp) == a.ndim, (arch, key, sp, a.shape)
            assert sp[engine.arena_block_axis(a, key)] is None, \
                "the block axis never shards"
            want = "model" if cfg.n_kv_heads % ms["model"] == 0 else None
            if key in ("k", "v"):
                assert sp[-2] == want, (arch, key, sp)


# ==========================================================================
# Cross-slice migration: a live request moves mid-decode and keeps
# producing the oracle's bits; sharing re-establishes on the destination.
# ==========================================================================

@pytest.mark.parametrize("family", ["decoder", "hybrid", "encdec"])
def test_migration_mid_decode_bitwise(family):
    """Decode 3 steps on slice A, migrate the request to slice B, decode 3
    more: B's lane must continue the oracle's logits bit for bit (covers
    plain KV, hybrid conv/SSM state rows, and encdec cross-K/V)."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(21)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 9)]
    mk = lambda mesh=None: make_adapter(
        cfg, params, n_slots=2, max_len=24, extras=extras, paged=True,
        block_size=BS, mesh=mesh)
    oracle = mk()
    A, B = mk(_slice_mesh(0)), mk(_slice_mesh(1))
    active = np.asarray([True, True])
    for slot, p in enumerate(prompts):
        assert oracle.insert(slot, p, max_new=8) == \
            A.insert(slot, p, max_new=8)
    for step in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        np.testing.assert_array_equal(oracle.decode(forced, active),
                                      A.decode(forced, active))
    live = -(-int(A.lens[1]) // BS)
    receipt = migrate_slot(A, 1, B, 1, prompts[1])
    # only blocks holding written rows cross the host; the pre-allocated
    # generation tail is re-created empty on the destination
    assert receipt.blocks_moved == live > 0
    assert receipt.blocks_total == len(B.slot_bids[1]) > live
    assert not A.slot_bids[1]                     # source slot released
    # the prompt's full blocks are now hit-able on the destination
    n_full = len(prompts[1]) // BS
    hits, _, _, _ = B.pool.match_prefix(prompts[1], count=False)
    assert len(hits) == n_full
    lane1 = np.asarray([False, True])
    for step in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        to = oracle.decode(forced, active)
        tb = B.decode(forced, lane1)
        np.testing.assert_array_equal(to[1:], tb[1:])
        np.testing.assert_array_equal(np.asarray(oracle.last_logits)[1],
                                      np.asarray(B.last_logits)[1])


def test_migration_preserves_sharing_and_cow():
    """Two requests sharing a full-block prefix: migrating one must leave
    the sibling's shared blocks bit-identical on the source, register the
    chain on the destination, and a second migration of the sibling must
    re-share those blocks there (referenced, not copied)."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(31)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS).astype(np.int32)
    p0 = np.concatenate([prefix, rng.integers(0, cfg.vocab, size=3,
                                              dtype=np.int32)])
    p1 = np.concatenate([prefix, rng.integers(0, cfg.vocab, size=5,
                                              dtype=np.int32)])
    mk = lambda mesh=None: make_adapter(cfg, params, n_slots=2, max_len=24,
                                        paged=True, block_size=BS, mesh=mesh)
    oracle, A, B = mk(), mk(_slice_mesh(0)), mk(_slice_mesh(1))
    for slot, p in enumerate((p0, p1)):
        assert oracle.insert(slot, p, max_new=8) == \
            A.insert(slot, p, max_new=8)
    shared_bids = A.slot_bids[0][:2]
    assert shared_bids == A.slot_bids[1][:2]      # prefix blocks shared
    assert all(A.pool.refcount[b] == 2 for b in shared_bids)
    before = {(key, b): np.asarray(A.arena_block(key, b))
              for b in shared_bids for key in A.seq_keys}
    live1 = -(-int(A.lens[1]) // BS)
    r1 = migrate_slot(A, 1, B, 1, p1)
    assert r1.blocks_shared == 0 and r1.blocks_moved == live1
    # source sibling untouched: refcounts dropped, bytes identical
    assert all(A.pool.refcount[b] == 1 for b in shared_bids)
    for (key, b), val in before.items():
        np.testing.assert_array_equal(val, np.asarray(A.arena_block(key, b)))
    # sibling keeps decoding the oracle's bits on the source
    active = np.asarray([True, True])
    lane0 = np.asarray([True, False])
    for step in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        to = oracle.decode(forced, active)
        ta = A.decode(forced, lane0)
        np.testing.assert_array_equal(to[:1], ta[:1])
    # second migration: the destination now owns the chain — shared blocks
    # are referenced there, not copied again
    live0 = -(-int(A.lens[0]) // BS)
    r0 = migrate_slot(A, 0, B, 0, p0)
    assert r0.blocks_shared == 2
    assert r0.blocks_moved == live0 - 2 < r1.blocks_moved
    assert all(B.pool.refcount[b] == 2
               for b in B.slot_bids[0][:2])


# ==========================================================================
# The router: affinity routing, spill to a non-owning slice, rebalancing
# migration inside the serving loop, telemetry.
# ==========================================================================

def _mk_gateway(cfg, params, n_slices, *, n_slots=2, num_blocks=None,
                max_new=4, auto_rebalance=True, max_queue=128):
    slices = build_slices(cfg, params,
                          [_slice_mesh(i) for i in range(n_slices)],
                          n_slots=n_slots, max_len=16, block_size=BS,
                          num_blocks=num_blocks)
    return ShardedPromptGateway(slices, max_new_tokens=max_new,
                                max_queue=max_queue,
                                auto_rebalance=auto_rebalance)


def test_router_affinity_then_spill_to_non_owning_slice():
    """Request 1 seeds a prefix on its slice; request 2 (same prefix, idle
    gateway) must route there by affinity; request 3 (same prefix, owning
    slice saturated) must spill to a non-owning slice and still complete
    with the oracle's tokens — the hit is an optimization, never a
    correctness dependency."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(41)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab, size=3, dtype=np.int32)
             for _ in range(3)]
    prompts = [np.concatenate([prefix, t]) for t in tails]
    gw = _mk_gateway(cfg, params, 2, n_slots=1, auto_rebalance=False)

    i0 = gw.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=4))
    gw.slices[i0].batcher.run()
    assert gw.routing["load"] == 1
    # idle owning slice -> affinity
    i1, reason = gw.route(prompts[1], 4)
    assert (i1, reason) == (i0, "affinity")
    gw.submit(Request(uid=1, prompt=prompts[1], max_new_tokens=4))
    # saturate the owning slice: its one slot is busy and a request queues
    busy = Request(uid=2, prompt=prompts[2], max_new_tokens=5)
    gw.slices[i0].batcher.submit(busy)
    gw.slices[i0].batcher.step()
    gw.slices[i0].batcher.submit(
        Request(uid=3, prompt=rng.integers(0, cfg.vocab, size=5,
                                           dtype=np.int32),
                max_new_tokens=4))
    i2, reason = gw.route(prompts[1], 4)
    assert reason == "affinity_spill" and i2 != i0
    req = Request(uid=4, prompt=prompts[1], max_new_tokens=4)
    assert gw.submit(req) != i0
    gw.slices[i2].batcher.run()
    # spilled request produced the oracle's tokens despite the cold slice
    oracle_ad = make_adapter(cfg, params, n_slots=1, max_len=16,
                             paged=True, block_size=BS)
    ob = ContinuousBatcher(oracle_ad)
    oreq = Request(uid=99, prompt=prompts[1], max_new_tokens=4)
    ob.submit(oreq)
    ob.run()
    assert req.generated == oreq.generated


def test_router_run_rebalances_and_conserves_energy():
    """A long-running request (A) blocks its slice while an affinity-routed
    sibling (C) queues behind it; the other slice drains and goes idle.
    The serving loop's rebalancer must migrate A onto the idle slice
    (unblocking C's admission onto the warm prefix), complete everything,
    charge the migration bytes into the (conserved) energy ledger, and
    report per-slice pool snapshots + routing counters."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(51)
    gw = _mk_gateway(cfg, params, 2, n_slots=1, num_blocks=9, max_new=4)
    prefix = rng.integers(0, cfg.vocab, size=2 * BS).astype(np.int32)
    a = Request(uid=0, prompt=prefix, max_new_tokens=8)
    assert gw.submit(a) == 0               # empty gateway: least-loaded
    gw.slices[0].batcher.step()            # admit A (indexes the prefix)
    b = Request(uid=1, prompt=rng.integers(0, cfg.vocab, size=6,
                                           dtype=np.int32),
                max_new_tokens=2)
    assert gw.submit(b) == 1               # load routing avoids slice 0
    c = Request(uid=2, prompt=np.concatenate(
        [prefix, rng.integers(0, cfg.vocab, size=3, dtype=np.int32)]),
        max_new_tokens=2)
    assert gw.submit(c) == 0               # affinity: A's slice owns it
    assert len(gw.slices[0].batcher.pending) == 1   # queued behind A
    tel = gw.run([])                       # drain under auto-rebalance
    tel.assert_conserved()
    rep = tel.report(1.0, kind="prompt")
    assert rep["completed"] == 3
    # B drained slice 1 and went idle while C queued behind A -> the
    # rebalancer moved A over, and C admitted onto the warm prefix
    assert gw.migrations >= 1
    assert a.migrations >= 1 and a.migration_bytes > 0
    assert c.prefill_tokens_skipped > 0
    assert rep["routing"]["migrations"] == gw.migrations
    assert rep["routing"]["migration_bytes"] == gw.migration_bytes > 0
    assert rep["migration_bytes_total"] == gw.migration_bytes
    assert set(rep["pools"]) == {0, 1}
    assert rep["pool"]["n_slices"] == 2
    migrated = [r for r in tel.records if r.migration_bytes > 0]
    assert migrated and sum(r.migration_bytes for r in migrated) == \
        gw.migration_bytes


# ==========================================================================
# Forced 8-device mesh: real multi-device slices (the sharded CI job).
# ==========================================================================

@multi
def test_serving_mesh_factors_into_slices():
    mesh = make_serving_mesh(8, model=1)
    subs = slice_meshes(mesh)
    assert len(subs) == 8
    assert len({list(m.devices.flat)[0].id for m in subs}) == 8
    assert mesh_shape_dict(mesh) == {"data": 8, "model": 1}
    mesh2 = make_serving_mesh(4, model=2)
    subs2 = slice_meshes(mesh2)
    assert len(subs2) == 4 and all(m.devices.size == 2 for m in subs2)


@multi
def test_router_multi_device_parity():
    """8 one-device slices, one request per slice (distinct prompts route
    by load): every request's generated tokens must equal a solo run on an
    unsharded adapter — per-lane bitwise independence carried across the
    whole mesh."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(61)
    mesh = make_serving_mesh(8, model=1)
    slices = build_slices(cfg, params, mesh, n_slots=2, max_len=16,
                          block_size=BS)
    gw = ShardedPromptGateway(slices, max_new_tokens=3,
                              auto_rebalance=False)
    prompts = [rng.integers(0, cfg.vocab, size=int(s), dtype=np.int32)
               for s in rng.integers(4, 10, size=8)]
    reqs = [Request(uid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    used = {gw.submit(r) for r in reqs}
    assert len(used) == 8                  # load routing spread the fleet
    while gw.busy:
        gw.step()
    oracle_ad = make_adapter(cfg, params, n_slots=2, max_len=16,
                             paged=True, block_size=BS)
    for i, p in enumerate(prompts):
        ob = ContinuousBatcher(oracle_ad)
        oreq = Request(uid=100 + i, prompt=p, max_new_tokens=3)
        ob.submit(oreq)
        ob.run()
        assert reqs[i].generated == oreq.generated, i


@multi
def test_aggregate_slots_exceed_single_device():
    """Acceptance: at a fixed per-device block budget, 8 slices sustain
    more concurrent slots than one device with the same budget."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(71)
    budget = 9                            # 8 usable blocks per device
    prompts = [rng.integers(0, cfg.vocab, size=6, dtype=np.int32)
               for _ in range(16)]
    arrivals = [Arrival(uid=i, t=0.0, endpoint=0, kind="prompt", payload=p)
                for i, p in enumerate(prompts)]
    single = make_adapter(cfg, params, n_slots=8, max_len=16, paged=True,
                          block_size=BS, num_blocks=budget)
    sb = ContinuousBatcher(single)
    for i, p in enumerate(prompts):
        sb.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    sb.run()
    mesh = make_serving_mesh(8, model=1)
    slices = build_slices(cfg, params, mesh, n_slots=8, max_len=16,
                          block_size=BS, num_blocks=budget)
    gw = ShardedPromptGateway(slices, max_new_tokens=4, max_queue=128)
    gw.run(arrivals)
    assert gw.peak_active_total() > sb.peak_active


@multi
def test_model_axis_sharded_slice_decodes():
    """A 2-device tensor-parallel slice (KV heads sharded over "model"
    when divisible) must produce the unsharded tokens; logits agree to
    float tolerance (cross-device reductions may reorder sums, so this is
    deliberately NOT a bitwise pin — docs/sharding.md spells out the
    parity boundary)."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(81)
    mesh2 = make_serving_mesh(1, model=2)
    sm = slice_meshes(mesh2)[0]
    assert sm.devices.size == 2
    un = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS)
    sh = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS, mesh=sm)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 7)]
    for slot, p in enumerate(prompts):
        assert un.insert(slot, p, max_new=4) == sh.insert(slot, p, max_new=4)
    active = np.asarray([True, True])
    for step in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        tu = un.decode(forced, active)
        ts = sh.decode(forced, active)
        np.testing.assert_array_equal(tu, ts)
        np.testing.assert_allclose(np.asarray(sh.last_logits),
                                   np.asarray(un.last_logits),
                                   rtol=1e-5, atol=1e-5)
