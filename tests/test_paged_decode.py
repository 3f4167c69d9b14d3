"""In-place (gather-free) paged decode: bitwise parity against the gather
tick and the dense adapter for all four attention families, decode at block
boundaries, out-of-range lane routing, the full-chain-gather-is-gone jaxpr
pin, and the Pallas-kernel tick."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from repro import configs
from repro.models import lm
from repro.serve.gateway.slots import make_adapter

FAMILY_ARCH = {                      # one arch per attention family
    "decoder": "stablelm_3b",        # causal MHA
    "moe": "deepseek_moe_16b",       # causal + routed FFN
    "hybrid": "hymba_1_5b",          # sliding windows + GQA + SSM state
    "encdec": "whisper_medium",      # causal self + cross attention
    "mla": "moonshot_v1_16b_a3b",    # latent attention + sigmoid-routed FFN
}
BS = 4


def _setup(arch):
    cfg = dataclasses.replace(configs.smoke_config(arch),
                              param_dtype="float32")
    params, _ = lm.init(jax.random.key(0), cfg, {})
    extras = None
    if cfg.family == "encdec":
        rng = np.random.default_rng(99)
        enc = jnp.asarray(rng.normal(0, 1, (1, cfg.enc_len, cfg.d_model)),
                          jnp.float32)
        extras = lambda: {"enc_embed": enc}
    elif cfg.family == "vlm":
        rng = np.random.default_rng(98)
        vis = jnp.asarray(
            rng.normal(0, 1, (1, cfg.n_vision_tokens, cfg.d_model)),
            jnp.float32)
        extras = lambda: {"vision_embed": vis}
    return cfg, params, extras


def _chain_blocks(ad, slot):
    return {(key, j): np.asarray(ad.arena_block(key, bid))
            for j, bid in enumerate(ad.slot_bids[slot])
            for key in ad.seq_keys}


# ==========================================================================
# Tentpole acceptance: the in-place tick is bitwise-identical to both
# oracles — the PR 2 gather tick and the dense adapter — per family.
# ==========================================================================

@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_inplace_matches_gather_tick_bitwise(family):
    """Same inserts, same forced tokens: the gather-free tick must produce
    the gather tick's logits, arena blocks, and non-sequence state bit for
    bit, every step."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 9)]
    adapters = [make_adapter(cfg, params, n_slots=2, max_len=24,
                             extras=extras, paged=True, block_size=BS,
                             inplace=ip) for ip in (True, False)]
    assert adapters[0].inplace and not adapters[1].inplace
    for slot, p in enumerate(prompts):
        toks = [ad.insert(slot, p, max_new=8) for ad in adapters]
        assert toks[0] == toks[1]
    active = np.asarray([True, True])
    for step in range(6):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        outs = [ad.decode(forced, active) for ad in adapters]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(np.asarray(adapters[0].last_logits),
                                      np.asarray(adapters[1].last_logits))
    inp, gat = adapters
    assert inp.slot_bids == gat.slot_bids
    for slot in range(2):
        a, b = _chain_blocks(inp, slot), _chain_blocks(gat, slot)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=str(key))
    for key in inp.cache:
        np.testing.assert_array_equal(np.asarray(inp.cache[key]),
                                      np.asarray(gat.cache[key]))


@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_inplace_matches_dense_adapter_bitwise(family):
    """The in-place tick against the *dense* oracle: one-shot admission
    (``chunked=False`` shares the dense adapter's prefill executable), then
    every decode step's logits must match bit for bit — causal, windowed
    (hybrid respects the trailing-``window`` bound), GQA, and encdec cross
    attention all ride through the block tables."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (6, 9)]
    paged = make_adapter(cfg, params, n_slots=2, max_len=24, extras=extras,
                         paged=True, block_size=BS, chunked=False)
    dense = make_adapter(cfg, params, n_slots=2, max_len=24, extras=extras)
    assert paged.inplace
    for slot, p in enumerate(prompts):
        assert paged.insert(slot, p, max_new=8) == dense.insert(slot, p)
    active = np.asarray([True, True])
    for step in range(6):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        tp = paged.decode(forced, active)
        td = dense.decode(forced, active)
        np.testing.assert_array_equal(tp, td)
        np.testing.assert_array_equal(np.asarray(paged.last_logits),
                                      np.asarray(dense.last_logits))


# ==========================================================================
# int8 kv_quant rides the in-place tick (quantized one-row write +
# dequantize inside the attention read) — bitwise against the gather tick,
# which vmaps the dense quant decode_step.
# ==========================================================================

@pytest.mark.parametrize("family", ["decoder", "hybrid"])
def test_kvquant_inplace_matches_gather_tick_bitwise(family):
    """cfg.kv_quant=True: the in-place tick quantizes the new K/V row
    post-RoPE, writes int8 rows + f32 scale rows, and dequantizes the
    gathered view in the read — the gather tick's bits, every step, for
    the quantized arenas too."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    cfg = dataclasses.replace(cfg, kv_quant=True)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 9)]
    adapters = [make_adapter(cfg, params, n_slots=2, max_len=24,
                             extras=extras, paged=True, block_size=BS,
                             inplace=ip) for ip in (True, False)]
    assert adapters[0].inplace and not adapters[1].inplace
    assert not adapters[0].kernel            # quant: XLA reference only
    assert {"k_scale", "v_scale"} <= set(adapters[0].seq_keys)
    for slot, p in enumerate(prompts):
        toks = [ad.insert(slot, p, max_new=8) for ad in adapters]
        assert toks[0] == toks[1]
    active = np.asarray([True, True])
    for step in range(5):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        outs = [ad.decode(forced, active) for ad in adapters]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(np.asarray(adapters[0].last_logits),
                                      np.asarray(adapters[1].last_logits))
    inp, gat = adapters
    assert inp.slot_bids == gat.slot_bids
    for slot in range(2):
        a, b = _chain_blocks(inp, slot), _chain_blocks(gat, slot)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=str(key))


# ==========================================================================
# vlm's grouped cache rides the in-place tick (PR 8: the last gather-tick
# fallback is gone) — bitwise against the kept gather oracle.
# ==========================================================================

def test_vlm_inplace_matches_gather_tick_bitwise():
    """The grouped layout (two leading layer axes on self k/v, one on the
    cross-layer self k/v) decodes through the generalized in-place row
    write: the gather tick's logits, arena blocks, and slot state bit for
    bit, every step."""
    cfg, params, extras = _setup("llama32_vision_90b")
    assert cfg.family == "vlm"
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 9)]
    adapters = [make_adapter(cfg, params, n_slots=2, max_len=24,
                             extras=extras, paged=True, block_size=BS,
                             inplace=ip) for ip in (True, False)]
    assert adapters[0].inplace and not adapters[1].inplace
    assert not adapters[0].kernel          # grouped layout: XLA reference
    assert {"kx_self", "vx_self"} <= set(adapters[0].seq_keys)
    for slot, p in enumerate(prompts):
        toks = [ad.insert(slot, p, max_new=8) for ad in adapters]
        assert toks[0] == toks[1]
    active = np.asarray([True, True])
    for step in range(5):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        outs = [ad.decode(forced, active) for ad in adapters]
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(np.asarray(adapters[0].last_logits),
                                      np.asarray(adapters[1].last_logits))
    inp, gat = adapters
    assert inp.slot_bids == gat.slot_bids
    for slot in range(2):
        a, b = _chain_blocks(inp, slot), _chain_blocks(gat, slot)
        for key in a:
            np.testing.assert_array_equal(a[key], b[key], err_msg=str(key))
    for key in inp.cache:
        np.testing.assert_array_equal(np.asarray(inp.cache[key]),
                                      np.asarray(gat.cache[key]))


def test_vlm_explicit_kernel_rejected():
    """kernel=True is a contract; the grouped layout must refuse it loudly
    instead of silently measuring the XLA path."""
    cfg, params, extras = _setup("llama32_vision_90b")
    with pytest.raises(ValueError, match="vlm"):
        make_adapter(cfg, params, n_slots=1, max_len=8, extras=extras,
                     paged=True, block_size=BS, kernel=True)


# ==========================================================================
# Block-boundary cases (satellite): aligned crossing, last writable
# position, trash-padded short chains.
# ==========================================================================

@pytest.mark.parametrize("family", sorted(FAMILY_ARCH))
def test_decode_block_boundary_cases(family):
    """Three lanes decoding together against the dense oracle, bitwise:
    a block-aligned prompt (len % bs == 0, first decode crosses into a
    freshly inserted block), a prompt at max_len - 1 (the last writable
    position), and a short trash-padded chain."""
    cfg, params, extras = _setup(FAMILY_ARCH[family])
    rng = np.random.default_rng(3)
    max_len = 16
    lens = (8, 15, 3)       # aligned | last writable | trash-padded short
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in lens]
    paged = make_adapter(cfg, params, n_slots=3, max_len=max_len,
                         extras=extras, paged=True, block_size=BS,
                         chunked=False)
    dense = make_adapter(cfg, params, n_slots=3, max_len=max_len,
                         extras=extras)
    for slot, p in enumerate(prompts):
        # reserve enough generation blocks to actually decode (slot 1 can
        # only ever take one more token: 15 + 1 == max_len)
        max_new = min(8, max_len - len(p))
        assert paged.insert(slot, p, max_new=max_new) == dense.insert(slot, p)
    # step 1: slot 1 writes position 15 — the last position its final
    # block holds; slot 0 writes position 8, the first row of the fresh
    # generation block its table got at admission
    active = np.asarray([True, True, True])
    forced = rng.integers(0, cfg.vocab, size=3).astype(np.int32)
    np.testing.assert_array_equal(paged.decode(forced, active),
                                  dense.decode(forced, active))
    np.testing.assert_array_equal(np.asarray(paged.last_logits),
                                  np.asarray(dense.last_logits))
    assert paged.at_capacity(1)
    # steps 2-3: slot 1 is retired (at capacity) — the oracle must mask it
    # too, since its dense cache would clamp the out-of-range write; slots
    # 0 and 2 keep decoding across their block boundaries
    active = np.asarray([True, False, True])
    for step in range(3):
        forced = rng.integers(0, cfg.vocab, size=3).astype(np.int32)
        tp = paged.decode(forced, active)
        td = dense.decode(forced, active)
        np.testing.assert_array_equal(tp[active], td[active])
        np.testing.assert_array_equal(
            np.asarray(paged.last_logits)[active],
            np.asarray(dense.last_logits)[active])
    assert int(paged.lens[0]) == 12 and int(paged.lens[2]) == 7


# ==========================================================================
# Out-of-range lanes route to the trash block *inside* the jitted tick
# (satellite bugfix: the old clamp aliased them onto the final block).
# ==========================================================================

@pytest.mark.parametrize("inplace", [True, False])
def test_oor_lane_routes_to_trash_in_jit(inplace):
    """Bypass the host-side at_capacity masking and hand the jitted tick an
    out-of-range length with a *real* write-block id: the write must land
    in the trash block, leaving the final (possibly shared) block intact.
    The pre-fix gather tick clamped the extraction slice instead, silently
    overwriting the final block."""
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab, size=7).astype(np.int32)
    ad = make_adapter(cfg, params, n_slots=1, max_len=8, paged=True,
                      block_size=BS, inplace=inplace)
    ad.insert(0, prompt, max_new=1)
    final_bid = int(ad.tables[0, ad.nb_max - 1])
    assert final_bid != 0
    dense = dict(ad.cache)
    dense["len"] = dense["len"].at[0].set(ad.max_len)      # out of range
    before = {key: np.asarray(ad.arena_block(key, final_bid))
              for key in ad.seq_keys}
    arena2, _, _ = ad._decode(
        ad.params, ad.arena, dense, jnp.asarray(ad.tables),
        jnp.asarray([[5]], jnp.int32), jnp.asarray([True]),
        jnp.asarray([final_bid], jnp.int32))               # a REAL target
    for key in ad.seq_keys:
        np.testing.assert_array_equal(
            before[key],
            np.asarray(jnp.take(arena2[key], final_bid,
                                axis=ad._bax[key])))


# ==========================================================================
# The full-chain gather is gone from the steady-state tick (jaxpr pin).
# ==========================================================================

def _gather_out_sizes(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.extend(int(np.prod(v.aval.shape)) for v in eqn.outvars)
        for p in eqn.params.values():
            vals = p if isinstance(p, (list, tuple)) else (p,)
            for v in vals:
                if isinstance(v, jex_core.ClosedJaxpr):
                    _gather_out_sizes(v.jaxpr, out)
                elif isinstance(v, jex_core.Jaxpr):
                    _gather_out_sizes(v, out)
    return out


def test_full_chain_gather_gone_from_inplace_tick():
    """The old tick materialized each key's whole (slots, L, nb_max*bs)
    dense cache through one giant gather; the in-place tick must never
    produce a gather that large — its reads are per-layer (XLA reference)
    or per-block (kernel DMA)."""
    cfg, params, _ = _setup("stablelm_3b")
    ad = make_adapter(cfg, params, n_slots=2, max_len=32, paged=True,
                      block_size=BS)
    args = (ad.params, ad.arena, ad.cache, jnp.asarray(ad.tables),
            jnp.zeros((2, 1), jnp.int32), jnp.ones((2,), bool),
            jnp.zeros((2,), jnp.int32))
    full_chain = (2 * cfg.n_layers * ad.nb_max * ad.bs
                  * cfg.n_kv_heads * cfg.d_head)
    new = _gather_out_sizes(jax.make_jaxpr(ad._decode)(*args).jaxpr, [])
    assert new and max(new) < full_chain
    # guard the pin itself: the legacy tick DOES contain that gather
    old = _gather_out_sizes(jax.make_jaxpr(ad._tick_impl)(*args).jaxpr, [])
    assert max(old) >= full_chain


# ==========================================================================
# The Pallas kernel tick (forced interpret off-TPU; the CI
# kernels-interpret leg runs this deliberately).
# ==========================================================================

@pytest.mark.parametrize("arch", ["stablelm_3b", "hymba_1_5b"])
def test_kernel_tick_matches_reference(arch):
    """kernel=True routes every self-attention layer through
    kernels/paged_attn.py inside the serving tick.  The kernel's online
    softmax is not bitwise against the single-shot reference, but tokens
    must agree and logits must be close — including hymba's traced
    per-layer sliding/global window selection."""
    cfg, params, extras = _setup(arch)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (5, 9)]
    ref = make_adapter(cfg, params, n_slots=2, max_len=16, extras=extras,
                       paged=True, block_size=BS, kernel=False)
    ker = make_adapter(cfg, params, n_slots=2, max_len=16, extras=extras,
                       paged=True, block_size=BS, kernel=True)
    assert ker.kernel and ker.inplace
    for slot, p in enumerate(prompts):
        assert ref.insert(slot, p, max_new=4) == ker.insert(slot, p,
                                                            max_new=4)
    active = np.asarray([True, True])
    for step in range(3):
        forced = rng.integers(0, cfg.vocab, size=2).astype(np.int32)
        tr = ref.decode(forced, active)
        tk = ker.decode(forced, active)
        np.testing.assert_array_equal(tr, tk)
        np.testing.assert_allclose(np.asarray(ker.last_logits),
                                   np.asarray(ref.last_logits),
                                   rtol=2e-4, atol=2e-4)


# ==========================================================================
# The arena's HBM format (kvcache/paged.py arena_formats): row-major where
# a Pallas kernel touches the arena, declared by every program taking it.
# ==========================================================================

@pytest.mark.parametrize("backend,sliced", [
    pytest.param("pallas", False, id="pallas"),
    pytest.param("xla", False, id="xla"),
    pytest.param("pallas", True, id="pallas-sliced")])
def test_arena_format_held_through_insert_cow_tick_and_migration(backend,
                                                                 sliced):
    """backend="pallas" pins the arena row-major: insert (``_scatter``),
    copy-on-write (``_copy``), the tick and a migration landing
    (``_write_block``) all hand it back in that format, every donated
    reference is deleted, and every program that takes the arena refuses
    one laid out otherwise instead of relayouting it.  backend="xla" declares
    no format and takes the arena as it comes.  ``sliced`` places the two
    adapters on one-device slice meshes of the sharded gateway, so the
    format rides a NamedSharding and the migration crosses slices.  The
    tokens follow the gather-tick oracle throughout (the logits bitwise
    under "xla", within the kernel's tolerance under "pallas")."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import Mesh, NamedSharding
    from repro.serve.shard.migrate import migrate_slot
    cfg, params, _ = _setup("stablelm_3b")
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    devs = jax.devices()
    mesh = lambda i: Mesh(np.asarray([devs[i % len(devs)]]), ("model",)) \
        if sliced else None
    mk = lambda b, i=0: make_adapter(cfg, params, n_slots=2, max_len=24,
                                     paged=True, block_size=BS,
                                     chunked=False, backend=b, mesh=mesh(i))
    ad, oracle, dst = mk(backend), mk("gather"), mk(backend, 1)
    row_major = {key: tuple(range(a.ndim)) for key, a in ad.arena.items()}
    if backend == "pallas":
        assert {key: f.layout.major_to_minor for key, f in
                ad.arena_format.items()} == row_major
    else:
        assert ad.arena_format is None

    def held(a, old):
        for arr in a.arena.values():
            assert arr.format.layout.major_to_minor == tuple(range(arr.ndim))
            assert isinstance(arr.sharding, NamedSharding) == sliced
            if sliced:
                assert arr.sharding.mesh == a.mesh
        assert all(arr.is_deleted() for arr in old.values())

    for slot in range(2):           # the second shares the partial block
        old = ad.arena
        assert ad.insert(slot, prompt, max_new=8) == \
            oracle.insert(slot, prompt, max_new=8)
        held(ad, old)
    assert ad.slot_stats(1)["prefix_hit_blocks"] == 2
    active = np.ones(2, bool)
    for toks in ([3, 7], [11, 2], [5, 5]):
        toks = np.asarray(toks, np.int32)
        old = ad.arena
        np.testing.assert_array_equal(ad.decode(toks, active),
                                      oracle.decode(toks, active))
        held(ad, old)
        got, want = (np.asarray(a.last_logits) for a in (ad, oracle))
        if backend == "xla":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert ad.pool.cow_copies >= 1
    # the slot state is committed beside the arena: one tick executable
    assert ad._decode._cache_size() == 1

    live = -(-int(ad.lens[0]) // BS)
    src_blocks = {k: v for k, v in _chain_blocks(ad, 0).items()
                  if k[1] < live}
    old = dst.arena
    migrate_slot(ad, 0, dst, 0, prompt)
    held(dst, old)
    for (key, j), blk in src_blocks.items():
        np.testing.assert_array_equal(
            np.asarray(dst.arena_block(key, dst.slot_bids[0][j])), blk)

    # the TPU's default for a head dim off the 128 lanes: blocks minor-most
    def foreign():
        return {key: jax.device_put(np.asarray(a), Format(
            Layout((0,) + tuple(range(2, a.ndim)) + (1,)), a.sharding))
            for key, a in dst.arena.items()}
    one = jnp.int32(1)
    padded = {key: jnp.zeros(a.shape[:1] + (1, dst.max_len) + a.shape[-2:],
                             a.dtype) for key, a in dst.arena.items()}
    dense = lambda: {key: jnp.array(a) for key, a in dst.cache.items()}
    programs = {
        "scatter": lambda ar: dst._scatter(
            ar, padded, jnp.zeros(dst.nb_max, jnp.int32)),
        "copy": lambda ar: dst._copy(ar, one, jnp.int32(2)),
        "write_block": lambda ar: dst._write_block(
            ar, one, {key: a[:, 2] for key, a in ar.items()}),
        "gather_prefix": lambda ar: dst._gather_prefix(
            ar, jnp.asarray([1], jnp.int32)),
        "decode": lambda ar: dst._decode(
            dst.params, ar, dense(), jnp.asarray(dst.tables),
            jnp.zeros((2, 1), jnp.int32), jnp.zeros(2, bool),
            jnp.zeros(2, jnp.int32)),
    }
    for name, call in programs.items():
        if backend == "pallas":
            with pytest.raises(ValueError, match="[Ll]ayout"):
                call(foreign())
        else:
            call(foreign())


@pytest.mark.parametrize("backend,sliced", [
    pytest.param("pallas", False, id="pallas"),
    pytest.param("xla", False, id="xla"),
    pytest.param("pallas", True, id="pallas-sliced")])
def test_latent_arena_through_insert_cow_tick_and_migration(backend, sliced):
    """The latent-attention arena, one row per token and layer with no head
    axis, through every path a K/V arena takes: held row-major under
    "pallas"; a second insert shares the first's prefix blocks (the full
    one and the partial one, which copy-on-write then splits); ticks
    follow the gather-tick oracle (bitwise under "xla", within the latent
    kernel's tolerance under "pallas"); and a migration to a second
    adapter, on a one-device slice mesh with ``sliced``, lands the chain's
    rows unchanged."""
    from jax.sharding import Mesh
    from repro.serve.shard.migrate import migrate_slot
    cfg, params, _ = _setup("moonshot_v1_16b_a3b")
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, cfg.vocab, size=6).astype(np.int32)
    devs = jax.devices()
    mesh = lambda i: Mesh(np.asarray([devs[i % len(devs)]]), ("model",)) \
        if sliced else None
    mk = lambda b, i=0: make_adapter(cfg, params, n_slots=2, max_len=24,
                                     paged=True, block_size=BS,
                                     chunked=False, backend=b, mesh=mesh(i))
    ad, oracle, dst = mk(backend), mk("gather"), mk(backend, 1)
    assert ad.seq_keys == ("latent",)
    assert ad.arena["latent"].shape[-3:] == (1, BS, cfg.latent_width)
    for a in (ad, dst):
        for arr in a.arena.values():
            layout = arr.format.layout.major_to_minor
            assert backend == "xla" or layout == tuple(range(arr.ndim))
    for slot in range(2):           # the second shares the partial block
        assert ad.insert(slot, prompt, max_new=8) == \
            oracle.insert(slot, prompt, max_new=8)
    assert ad.slot_stats(1)["prefix_hit_blocks"] == 2
    active = np.ones(2, bool)
    for toks in ([3, 7], [11, 2], [5, 5]):
        toks = np.asarray(toks, np.int32)
        np.testing.assert_array_equal(ad.decode(toks, active),
                                      oracle.decode(toks, active))
        got, want = (np.asarray(a.last_logits) for a in (ad, oracle))
        if backend == "xla":
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert ad.pool.cow_copies >= 1
    live = -(-int(ad.lens[0]) // BS)
    src_blocks = {k: v for k, v in _chain_blocks(ad, 0).items()
                  if k[1] < live}
    migrate_slot(ad, 0, dst, 0, prompt)
    for (key, j), blk in src_blocks.items():
        np.testing.assert_array_equal(
            np.asarray(dst.arena_block(key, dst.slot_bids[0][j])), blk)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_dropped_adapter_frees_its_arena(backend):
    """An adapter nobody holds is collected with its arena: a process that
    builds a second gateway (a run after a run, on the chip) must find the
    first one's device memory free.  Under "pallas" a wrapper of the jitted
    programs once hid a reference cycle from the collector."""
    import gc
    import weakref
    cfg, params, _ = _setup("stablelm_3b")
    ad = make_adapter(cfg, params, n_slots=2, max_len=16, paged=True,
                      block_size=BS, backend=backend)
    ad.insert(0, np.arange(5, dtype=np.int32), max_new=4)
    ad.decode(np.zeros(2, np.int32), np.asarray([True, False]))
    alive, shape = weakref.ref(ad), ad.arena["k"].shape
    del ad
    gc.collect()
    assert alive() is None
    assert not any(a.shape == shape for a in jax.live_arrays())


def test_pinned_arena_survives_the_persistent_compile_cache(tmp_path,
                                                            monkeypatch):
    """A warm run loads the adapter's programs from JAX's persistent
    compile cache, and an executable loaded from there labels its outputs
    with the default layout whatever layout it wrote (jax 0.9.0): the
    arena reached the next program in the default layout and was refused.
    The programs that return the arena stay out of that cache, and the
    warm run serves as the cold one did.  On the CPU the row-major pin is
    the default layout, so a block-minor pin stands in."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental.layout import Format, Layout
    from repro.serve.kvcache import paged
    minor = lambda nd: (0,) + tuple(range(2, nd)) + (1,)
    monkeypatch.setattr(
        paged, "arena_formats", lambda arena, backend, where:
        None if backend != "pallas" else
        {key: Format(Layout(minor(a.ndim)), where[key])
         for key, a in arena.items()})
    cfg, params, _ = _setup("stablelm_3b")
    prompt = np.random.default_rng(21).integers(
        0, cfg.vocab, size=5).astype(np.int32)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    try:
        for run in ("cold", "warm"):
            jax.clear_caches()
            ad = make_adapter(cfg, params, n_slots=1, max_len=16,
                              paged=True, block_size=BS, backend="pallas")
            ad.insert(0, prompt, max_new=4)
            ad.decode(np.asarray([3], np.int32), np.ones(1, bool))
            for a in ad.arena.values():
                assert a.format.layout.major_to_minor == minor(a.ndim), run
        assert any(tmp_path.iterdir())
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was[1])
        cc.reset_cache()

