"""Pallas paged decode-attention kernel vs the XLA gather reference
(interpret mode), plus the gather path's own masking semantics, the
sliding-window operand, and the REPRO_KERNELS_INTERPRET override."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.paged_attn import paged_decode_attention, scatter_kv_rows
from repro.nn import attention


def _make_case(rng, B, nb, bs, Hq, Hkv, D, num_blocks, dtype):
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, D)), dtype)
    k_arena = jnp.asarray(rng.normal(0, 1, (num_blocks, bs, Hkv, D)), dtype)
    v_arena = jnp.asarray(rng.normal(0, 1, (num_blocks, bs, Hkv, D)), dtype)
    # each row gets a distinct permutation of arena blocks (block 0 = trash)
    tables = np.zeros((B, nb), np.int32)
    lens = np.zeros((B,), np.int32)
    for b in range(B):
        lens[b] = int(rng.integers(1, nb * bs + 1))
        used = -(-int(lens[b]) // bs)
        tables[b, :used] = rng.choice(
            np.arange(1, num_blocks), size=used, replace=False)
    return q, k_arena, v_arena, jnp.asarray(tables), jnp.asarray(lens)


@pytest.mark.parametrize("B,nb,bs,Hq,Hkv,D,dtype", [
    (3, 4, 8, 4, 4, 32, jnp.float32),       # MHA
    (2, 3, 16, 8, 2, 64, jnp.float32),      # GQA 4:1
    (4, 2, 8, 6, 6, 16, jnp.bfloat16),
    (1, 5, 4, 4, 1, 32, jnp.float32),       # MQA
])
def test_paged_kernel_matches_gather_reference(B, nb, bs, Hq, Hkv, D, dtype):
    rng = np.random.default_rng(B * nb * bs)
    num_blocks = B * nb + 1
    q, ka, va, tables, lens = _make_case(rng, B, nb, bs, Hq, Hkv, D,
                                         num_blocks, dtype)
    got = paged_decode_attention(q, ka, va, tables, lens, interpret=True)
    want = attention.attend_decode_paged(q[:, None], ka, va, tables, lens)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want[:, 0], np.float32),
                               rtol=tol, atol=tol)


def test_gather_reference_matches_dense_attend_decode():
    """attend_decode_paged == attend_decode on the densely-laid-out cache:
    paging is a pure relayout, not a different attention."""
    rng = np.random.default_rng(0)
    B, nb, bs, Hq, Hkv, D = 2, 3, 8, 4, 2, 32
    S = nb * bs
    k = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(0, 1, (B, S, Hkv, D)), jnp.float32)
    q = jnp.asarray(rng.normal(0, 1, (B, 1, Hq, D)), jnp.float32)
    # identity block layout: row b owns blocks [1 + b*nb, 1 + (b+1)*nb)
    k_arena = jnp.concatenate(
        [jnp.zeros((1, bs, Hkv, D))] + [k[b].reshape(nb, bs, Hkv, D)
                                        for b in range(B)]).astype(k.dtype)
    v_arena = jnp.concatenate(
        [jnp.zeros((1, bs, Hkv, D))] + [v[b].reshape(nb, bs, Hkv, D)
                                        for b in range(B)]).astype(v.dtype)
    tables = jnp.asarray([[1 + b * nb + j for j in range(nb)]
                          for b in range(B)], jnp.int32)
    for ln in (1, bs, S - 3, S):
        want = attention.attend_decode(q, k, v, ln)
        got = attention.attend_decode_paged(
            q, k_arena, v_arena, tables, jnp.full((B,), ln, jnp.int32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window", [3, 8, 17])
def test_paged_kernel_windowed_matches_reference(window):
    """The kernel's trailing-window mask (scalar-prefetch operand) must
    agree with attend_decode_paged's — only positions in
    [lens - window, lens) attend, whatever blocks the table routes."""
    rng = np.random.default_rng(window)
    B, nb, bs, Hq, Hkv, D = 3, 4, 8, 4, 2, 32
    num_blocks = B * nb + 1
    q, ka, va, tables, lens = _make_case(rng, B, nb, bs, Hq, Hkv, D,
                                         num_blocks, jnp.float32)
    got = paged_decode_attention(q, ka, va, tables, lens, window=window,
                                 interpret=True)
    want = attention.attend_decode_paged(q[:, None], ka, va, tables, lens,
                                         window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               rtol=2e-5, atol=2e-5)


def test_paged_kernel_new_kv_splice_matches_insert():
    """attend_decode_paged(new_kv=...) — the in-place tick's read of the
    row it is mid-way through writing — must equal attending after the row
    was physically scattered into the arena."""
    rng = np.random.default_rng(11)
    B, nb, bs, Hq, Hkv, D = 2, 3, 4, 4, 2, 16
    num_blocks = B * nb + 1
    q, ka, va, _, _ = _make_case(rng, B, nb, bs, Hq, Hkv, D,
                                 num_blocks, jnp.float32)
    # fully-populated disjoint tables so every lane's new row (position
    # ``lens``, possibly the first row of a fresh block) has a real,
    # lane-private block to land in
    tables = jnp.asarray(
        rng.permutation(np.arange(1, num_blocks))[:B * nb].reshape(B, nb))
    lens = jnp.asarray([bs * 2, bs * 2 - 1], jnp.int32)  # boundary + mid
    k1 = jnp.asarray(rng.normal(0, 1, (B, Hkv, D)), jnp.float32)
    v1 = jnp.asarray(rng.normal(0, 1, (B, Hkv, D)), jnp.float32)
    # physically write the new row at position lens per lane
    ka2, va2 = ka, va
    for b in range(B):
        blk = int(tables[b, int(lens[b]) // bs])
        off = int(lens[b]) % bs
        ka2 = ka2.at[blk, off].set(k1[b])
        va2 = va2.at[blk, off].set(v1[b])
    want = attention.attend_decode_paged(q[:, None], ka2, va2, tables,
                                         lens + 1)
    got = attention.attend_decode_paged(q[:, None], ka, va, tables,
                                        lens + 1, new_kv=(k1, v1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the kernel's in-VMEM overlay must agree with the physical write too
    # (this is how the serving tick reads the row it is mid-way through
    # writing without copying the arena slice)
    kern = paged_decode_attention(q, ka, va, tables, lens + 1,
                                  new_kv=(k1, v1), interpret=True)
    kern_want = paged_decode_attention(q, ka2, va2, tables, lens + 1,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(kern_want))


@pytest.mark.parametrize("window", [None, 5, 13])
def test_whole_arena_layer_call_matches_per_layer_reference(window):
    """The serving tick hands the kernel the whole layer-leading arena
    ``(L, num_blocks, 1, bs, Hkv, D)`` and a layer index instead of a
    per-layer slice: for every layer, the kernel matches
    attend_decode_paged on that layer's slice and is bitwise its own
    single-layer call — over trash-padded tables, with and without sliding
    windows."""
    rng = np.random.default_rng(17 + (window or 0))
    L, B, nb, bs, Hq, Hkv, D = 3, 3, 4, 8, 4, 2, 32
    num_blocks = B * nb + 1
    q, _, _, tables, lens = _make_case(rng, B, nb, bs, Hq, Hkv, D,
                                       num_blocks, jnp.float32)
    shape = (L, num_blocks, 1, bs, Hkv, D)
    ka = jnp.asarray(rng.normal(size=shape), jnp.float32)
    va = jnp.asarray(rng.normal(size=shape), jnp.float32)
    k1 = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
    v1 = jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
    tol = dict(rtol=2e-5, atol=2e-5)
    for layer in range(L):
        ks, vs = ka[layer, :, 0], va[layer, :, 0]
        want = attention.attend_decode_paged(
            q[:, None], ks, vs, tables, lens, window=window or 0,
            new_kv=(k1, v1))[:, 0]
        got = paged_decode_attention(q, ka, va, tables, lens, window=window,
                                     new_kv=(k1, v1), layer=jnp.int32(layer),
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(
            paged_decode_attention(q, ks, vs, tables, lens, window=window,
                                   new_kv=(k1, v1), interpret=True)))


@pytest.mark.parametrize("tiles", [1, 4, None], ids=["t1", "t4", "tdef"])
@pytest.mark.parametrize("splice", [False, True], ids=["plain", "new_kv"])
@pytest.mark.parametrize("window", [None, 6])
def test_paged_kernel_reads_only_live_entries(window, splice, tiles):
    """Every block a table entry outside a lane's live entries ``[lo,
    last]`` points at holds NaN: before the window's first block, past the
    block of ``lens - 1``, and the trash block.  The sweep must neither
    compute nor fetch them — a masked NaN tile would still reach ``p @ V``
    — so every lane with ``lens > 0`` stays finite and equal to the gather
    reference over a clean arena.  Lanes end on, inside and just past a
    block, and (at 4 and the default entries a step) mid-step."""
    rng = np.random.default_rng(29)
    nb, bs, Hq, Hkv, D = 6, 4, 4, 2, 16
    lens = np.asarray([0, 1, bs, bs + 1, nb * bs - 1, nb * bs], np.int32)
    B = len(lens)
    num_blocks = 1 + 2 * B * nb
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    ka = rng.normal(size=(num_blocks, bs, Hkv, D)).astype(np.float32)
    va = rng.normal(size=(num_blocks, bs, Hkv, D)).astype(np.float32)
    # live entries take blocks 1 .. B*nb, every other entry a block of its
    # own past them; only the live ones keep finite contents
    tables = np.zeros((B, nb), np.int32)
    dead = np.ones(num_blocks, bool)
    for b, n in enumerate(lens):
        lo = max(n - (window or n), 0) // bs
        for e in range(nb):
            live = n > 0 and lo <= e <= (n - 1) // bs
            tables[b, e] = 1 + b * nb + e + (0 if live else B * nb)
            dead[tables[b, e]] &= not live
    tables[0, 0] = 0                           # and the trash block
    poisoned = lambda a: jnp.asarray(np.where(
        dead[:, None, None, None], np.nan, a))
    new_kv = None
    if splice:
        new_kv = tuple(jnp.asarray(rng.normal(size=(B, Hkv, D)), jnp.float32)
                       for _ in range(2))
    got = paged_decode_attention(q, poisoned(ka), poisoned(va),
                                 jnp.asarray(tables), jnp.asarray(lens),
                                 window=window, new_kv=new_kv, tiles=tiles,
                                 interpret=True)
    want = attention.attend_decode_paged(
        q[:, None], jnp.asarray(ka), jnp.asarray(va), jnp.asarray(tables),
        jnp.asarray(lens), window=window or 0, new_kv=new_kv)[:, 0]
    got, want = np.asarray(got)[lens > 0], np.asarray(want)[lens > 0]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_default_interpret_env_override(monkeypatch):
    """REPRO_KERNELS_INTERPRET forces the mode either way; unset falls
    back to the backend probe — what the CI kernels-interpret leg relies
    on to exercise the Pallas bodies deliberately."""
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "1")
    assert ops.default_interpret() is True
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "0")
    assert ops.default_interpret() is False
    monkeypatch.setenv("REPRO_KERNELS_INTERPRET", "false")
    assert ops.default_interpret() is False
    monkeypatch.delenv("REPRO_KERNELS_INTERPRET")
    import jax
    assert ops.default_interpret() is (jax.default_backend() != "tpu")
    assert ops.resolve_interpret(True) is True      # explicit always wins


def test_paged_kernel_ignores_trash_block_contents():
    """Positions masked by ``lens`` never reach the softmax, whatever the
    trash block or stale tail blocks hold."""
    rng = np.random.default_rng(3)
    B, nb, bs, H, D = 1, 3, 4, 2, 16
    q, ka, va, tables, lens = _make_case(rng, B, nb, bs, H, H, D, 8,
                                         jnp.float32)
    lens = jnp.asarray([5], jnp.int32)          # only block 0-1 partially live
    base = paged_decode_attention(q, ka, va, tables, lens, interpret=True)
    ka2 = ka.at[0].set(1e9)                     # poison the trash block
    va2 = va.at[0].set(-1e9)
    tables2 = jnp.asarray(tables).at[0, 2:].set(0)
    got = paged_decode_attention(q, ka2, va2, tables2, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-6, atol=1e-6)


# ==========================================================================
# The in-place arena-write kernel (input_output_aliasing): the Pallas leg
# of the decode tick's row scatter.
# ==========================================================================

def test_scatter_kv_rows_matches_at_set():
    """scatter_kv_rows == arena.at[:, wbids, 0, offs].set(rows) on unique
    (block, row) targets, leaving every unaddressed block bit-untouched —
    the aliased outputs start as the input buffers, so nothing is
    functionally rebuilt."""
    rng = np.random.default_rng(7)
    L, nb, bs, H, D, S = 3, 6, 4, 2, 8, 4
    ka = jnp.asarray(rng.normal(size=(L, nb, 1, bs, H, D)), jnp.float32)
    va = jnp.asarray(rng.normal(size=(L, nb, 1, bs, H, D)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(L, S, H, D)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(L, S, H, D)), jnp.float32)
    wbids = np.array([2, 5, 1, 3], np.int32)
    offs = np.array([1, 3, 0, 2], np.int32)
    nk, nv = scatter_kv_rows(ka, va, kr, vr, jnp.asarray(wbids),
                             jnp.asarray(offs), interpret=True)
    np.testing.assert_array_equal(
        np.asarray(nk), np.asarray(ka.at[:, wbids, 0, offs].set(kr)))
    np.testing.assert_array_equal(
        np.asarray(nv), np.asarray(va.at[:, wbids, 0, offs].set(vr)))
    # untouched blocks (0 and 4) keep their exact bytes
    for b in (0, 4):
        np.testing.assert_array_equal(np.asarray(nk[:, b]),
                                      np.asarray(ka[:, b]))


def test_scatter_kv_rows_trash_collisions_stay_in_trash():
    """Several masked lanes colliding on the trash block must not touch
    any real block — collisions are absorbed by block 0 in some order,
    which is garbage under every order."""
    rng = np.random.default_rng(8)
    L, nb, bs, H, D, S = 2, 4, 4, 1, 8, 3
    ka = jnp.asarray(rng.normal(size=(L, nb, 1, bs, H, D)), jnp.float32)
    va = jnp.asarray(rng.normal(size=(L, nb, 1, bs, H, D)), jnp.float32)
    kr = jnp.asarray(rng.normal(size=(L, S, H, D)), jnp.float32)
    vr = jnp.asarray(rng.normal(size=(L, S, H, D)), jnp.float32)
    wbids = np.array([0, 0, 2], np.int32)       # two lanes trash-routed
    offs = np.array([1, 1, 3], np.int32)        # ... colliding on one row
    nk, nv = scatter_kv_rows(ka, va, kr, vr, jnp.asarray(wbids),
                             jnp.asarray(offs), interpret=True)
    for b in (1, 3):                            # untouched real blocks
        np.testing.assert_array_equal(np.asarray(nk[:, b]),
                                      np.asarray(ka[:, b]))
    np.testing.assert_array_equal(               # lane 2's real write lands
        np.asarray(nk[:, 2, 0, 3]), np.asarray(kr[:, 2]))
    np.testing.assert_array_equal(
        np.asarray(nv[:, 2, 0, 3]), np.asarray(vr[:, 2]))


# ==========================================================================
# The latent (MLA) decode read and its row write.
# ==========================================================================

@pytest.mark.parametrize("nb,dtype", [(3, jnp.float32), (20, jnp.float32),
                                      (17, jnp.bfloat16),
                                      (300, jnp.float32)])
def test_latent_kernel_matches_gather_reference(nb, dtype):
    """The latent kernel against ``attend_decode_latent``'s XLA gather, on
    the whole layer-leading arena at each layer:
    tables shorter than, equal to and longer than one step's 8 entries, and
    of 300 entries (positions past 1024, as the Moonlight cell's 136
    entries of 16 reach), lanes ending anywhere in a step (entries past a lane's last live block
    are read as that block again and skipped), the new row overlaid at
    ``lens - 1``; the arena's other rows are noise the lengths mask."""
    from repro.kernels.paged_attn import latent_decode_attention
    rng = np.random.default_rng(nb)
    B, Hq, W, dv, bs, L = 5, 4, 80, 64, 4, 2
    num_blocks = B * nb + 1
    arena = jnp.asarray(rng.normal(0, 1, (L, num_blocks, 1, bs, W)), dtype)
    tables = jnp.asarray(rng.permutation(np.arange(1, num_blocks))
                         .reshape(B, nb), jnp.int32)
    lens = jnp.asarray([1, bs, bs + 1, nb * bs - 3, nb * bs], jnp.int32)
    q = jnp.asarray(rng.normal(0, 1, (B, Hq, W)), dtype)
    row = jnp.asarray(rng.normal(0, 1, (B, W)), dtype)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for layer in range(L):
        want = attention.attend_decode_latent(
            q, arena[layer, :, 0], tables, lens, new_row=row, scale=0.1,
            dv=dv)
        got = latent_decode_attention(q, arena, tables, lens, new_row=row,
                                      scale=0.1, dv=dv,
                                      layer=jnp.int32(layer), interpret=True)
        assert got.shape == (B, Hq, dv) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


def test_scatter_latent_rows_matches_at_set():
    from repro.kernels.paged_attn import scatter_latent_rows
    rng = np.random.default_rng(3)
    L, nblk, bs, W = 3, 9, 4, 80
    arena = jnp.asarray(rng.normal(0, 1, (L, nblk, 1, bs, W)), jnp.float32)
    rows = jnp.asarray(rng.normal(0, 1, (L, 4, W)), jnp.float32)
    wbids = jnp.asarray([3, 7, 5, 8], jnp.int32)
    offs = jnp.asarray([0, 3, 1, 2], jnp.int32)
    want = arena.at[:, wbids, 0, offs].set(rows)
    got = scatter_latent_rows(arena, rows, wbids, offs, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
