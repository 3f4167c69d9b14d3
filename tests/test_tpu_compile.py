"""Compile the main path's Pallas kernels for a described TPU v5e, at the
widths the served models use.  Nothing runs: Mosaic refuses here what it
would refuse on the chip (block shapes, layouts, unsupported ops), so these
tests guard the chip at no chip time.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.kernels import paged_attn as pk
from repro.models import lm
from repro.serve import engine
from repro.serve.kvcache import paged

# (Hq, Hkv, D): stablelm-3b (MHA) and hymba-1.5b (GQA, Hkv=5)
WIDTHS = {"stablelm-3b": (32, 32, 80), "hymba-1.5b": (25, 5, 64)}
LANES, NB, BS, BLOCKS = 8, 32, 16, 257     # 8 lanes x 512 tokens, block 16
LENET_PATCHES, LENET_K, LENET_O = 32 * 784, 25, 32   # LeNet-5 conv1
# the served tick of bench/configs/stablelm-3b.json: 16 lanes x 64 table
# entries of 16-token blocks, 448 blocks in the arena
TICK_LANES, TICK_NB, TICK_BLOCKS = 16, 64, 448
# Moonlight-16B-A3B's latent rows (kv_lora_rank 512 + rope 64) read by 16
# query heads, and the served tick of bench/configs/moonlight-16b-a3b.json:
# 9 of its 27 layers, 64 lanes x 136 entries, 8704 blocks
LATENT_HQ, LATENT_W, LATENT_DV = 16, 576, 512
MLA_LAYERS, MLA_LANES, MLA_NB, MLA_BLOCKS = 9, 64, 136, 8704


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes, **jit_kw):
    """Compile ``fn`` for the described chip; return the executable text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn, **jit_kw).lower(*args).compile().as_text()


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_kernel_with_new_row_compiles(one_chip, width):
    Hq, Hkv, D = WIDTHS[width]
    bf, i32 = jnp.bfloat16, jnp.int32
    text = _compile(
        one_chip,
        lambda q, k, v, t, ln, k1, v1: pk.paged_decode_attention(
            q, k, v, t, ln, window=jnp.int32(1024), new_kv=(k1, v1),
            interpret=False),
        ((LANES, Hq, D), bf), ((BLOCKS, BS, Hkv, D), bf),
        ((BLOCKS, BS, Hkv, D), bf), ((LANES, NB), i32), ((LANES,), i32),
        ((LANES, Hkv, D), bf), ((LANES, Hkv, D), bf))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_decode_kernel_compiles_at_the_served_tick(one_chip, width):
    """The whole-arena ``layer=`` call at the served tick's shape (32
    layers, 16 lanes x 64 entries of 16, 448 blocks) over the arena held
    row-major, as the adapter holds it: the default entries a grid step
    fit the v5e's VMEM and lower, with no relayout of the arena around
    the kernel.  Hymba's GQA width runs a traced sliding window."""
    Hq, Hkv, D = WIDTHS[width]
    bf, i32 = jnp.bfloat16, jnp.int32
    shape = jax.ShapeDtypeStruct((32, TICK_BLOCKS, 1, BS, Hkv, D), bf)
    fmt = paged.arena_formats({"k": shape}, "pallas", {"k": one_chip})["k"]
    arena = jax.ShapeDtypeStruct(shape.shape, bf, sharding=fmt)
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    window = width == "hymba-1.5b"
    tick = jax.jit(
        lambda q, k, v, t, ln, k1, v1, y, w: pk.paged_decode_attention(
            q, k, v, t, ln, window=w if window else None, new_kv=(k1, v1),
            layer=y, interpret=False)).lower(
        sds((TICK_LANES, Hq, D), bf), arena, arena,
        sds((TICK_LANES, TICK_NB), i32), sds((TICK_LANES,), i32),
        sds((TICK_LANES, Hkv, D), bf), sds((TICK_LANES, Hkv, D), bf),
        sds((), i32), sds((), i32)).compile()
    assert "tpu_custom_call" in tick.as_text()
    assert tick.memory_analysis().temp_size_in_bytes < 1e6


def test_scatter_kv_rows_compiles(one_chip):
    Hq, Hkv, D = WIDTHS["stablelm-3b"]
    arena = ((32, BLOCKS, 1, BS, Hkv, D), jnp.bfloat16)
    rows = ((32, LANES, Hkv, D), jnp.bfloat16)
    text = _compile(
        one_chip,
        lambda ka, va, kr, vr, w, o: pk.scatter_kv_rows(
            ka, va, kr, vr, w, o, interpret=False),
        arena, arena, rows, rows, ((LANES,), jnp.int32),
        ((LANES,), jnp.int32), donate_argnums=(0, 1))
    assert "tpu_custom_call" in text


def _cascade_call(kernel, Hq, Hkv, D):
    """(fn, operand shapes) of one cascade kernel: 2 groups of 4 lanes."""
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    arena = ((BLOCKS, BS, Hkv, D), bf)
    if kernel == "prefix":
        G, Lc = 2, 4
        return (lambda qg, k, v, gt, gl, ll: pk.cascade_prefix_attention(
                    qg, k, v, gt, gl, ll, window=jnp.int32(1024),
                    interpret=False),
                [((G, Lc, Hq, D), bf), arena, arena, ((G, 8), i32),
                 ((G,), i32), ((G, Lc), i32)])
    if kernel == "suffix":
        return (lambda q, k, v, t, ln, q0, k1, v1:
                    pk.paged_decode_attention_with_state(
                        q, k, v, t, ln, q0=q0, new_kv=(k1, v1),
                        interpret=False),
                [((LANES, Hq, D), bf), arena, arena, ((LANES, NB), i32),
                 ((LANES,), i32), ((LANES,), i32), ((LANES, Hkv, D), bf),
                 ((LANES, Hkv, D), bf)])
    acc, ml = ((LANES, Hq, D), f32), ((LANES, Hq), f32)
    return (lambda a1, m1, l1, a2, m2, l2: pk.merge_attn_states(
                a1, m1, l1, a2, m2, l2, interpret=False),
            [acc, ml, ml, acc, ml, ml])


@pytest.mark.parametrize("kernel", ["prefix", "suffix", "merge"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_cascade_kernel_compiles(one_chip, kernel, width):
    fn, shapes = _cascade_call(kernel, *WIDTHS[width])
    assert "tpu_custom_call" in _compile(one_chip, fn, *shapes)


@pytest.mark.parametrize("out_kernels", [LENET_O, 2 * LENET_O],
                         ids=["one_bank", "pos_neg_banks"])
@pytest.mark.parametrize("bits", [5, 8])
def test_sc_datapath_compiles_at_lenet_first_layer(one_chip, bits,
                                                   out_kernels):
    text = _compile(
        one_chip,
        lambda x, w: ops.sc_dot_from_levels(x, w, bits, interpret=False),
        ((LENET_PATCHES, LENET_K), jnp.int32),
        ((LENET_K, out_kernels), jnp.int32))
    assert text.count("tpu_custom_call") >= 3      # two SNG packs + the dot


def test_sng_pack_compiles(one_chip):
    N = 256
    text = _compile(
        one_chip,
        lambda lv, codes: ops.sng_pack(lv, codes, N, interpret=False),
        ((LENET_PATCHES * LENET_K,), jnp.int32), ((N,), jnp.int32))
    assert "tpu_custom_call" in text


def _arena_sized_ops(text, arena_shape):
    """The copies and slicing fusions of a compiled HLO ``text`` whose
    result has the shape of the whole arena or of one layer of it (unit
    dims ignored): the relayouts and per-layer slices the served tick must
    not hold."""
    drop = lambda dims: tuple(d for d in dims if d != 1)
    whole = drop(arena_shape)
    banned = {whole, whole[1:]}
    found = []
    for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z-]+)\(", text,
                         re.M):
        name, result, op = m.groups()
        if op not in ("copy", "copy-start", "fusion", "dynamic-slice") or \
                (op == "fusion" and "slice" not in name
                 and "copy" not in name):
            continue
        for dims in re.findall(r"\[([\d,]+)\]", result):
            if drop(tuple(int(d) for d in dims.split(","))) in banned:
                found.append(f"%{name} = {result}")
    return found


def test_latent_decode_kernel_compiles(one_chip):
    """The latent read at Moonlight's widths over the whole layer-leading
    arena, and its row write."""
    bf, i32 = jnp.bfloat16, jnp.int32
    arena = ((MLA_LAYERS, BLOCKS, 1, BS, LATENT_W), bf)
    text = _compile(
        one_chip,
        lambda q, a, t, ln, row, y: pk.latent_decode_attention(
            q, a, t, ln, new_row=row, scale=192 ** -0.5, dv=LATENT_DV,
            layer=y, interpret=False),
        ((LANES, LATENT_HQ, LATENT_W), bf), arena, ((LANES, NB), i32),
        ((LANES,), i32), ((LANES, LATENT_W), bf), ((), i32))
    assert "tpu_custom_call" in text
    text = _compile(
        one_chip,
        lambda a, r, w, o: pk.scatter_latent_rows(a, r, w, o,
                                                  interpret=False),
        arena, ((MLA_LAYERS, LANES, LATENT_W), bf), ((LANES,), i32),
        ((LANES,), i32), donate_argnums=(0,))
    assert "tpu_custom_call" in text


def _served_tick(one_chip, cfg, lanes, nb, blocks):
    """The adapter's Pallas tick for ``cfg`` compiled from abstract params
    and the arena held row-major: (executable, arena shapes)."""
    sds = lambda a, where=one_chip: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=where)
    params = jax.tree.map(sds, lm.init(jax.random.key(0), cfg, {},
                                       abstract=True)[0])
    shape = engine.init_paged_arena(cfg, blocks, BS, abstract=True)
    fmt = paged.arena_formats(shape, "pallas",
                              {key: one_chip for key in shape})
    assert paged.arena_formats(shape, "xla", {}) is None
    arena = {key: sds(a, fmt[key]) for key, a in shape.items()}
    dense = {key: jax.ShapeDtypeStruct((lanes,) + a.shape, a.dtype,
                                       sharding=one_chip)
             for key, a in engine.init_cache(cfg, 1, nb * BS,
                                             abstract=True).items()
             if key not in shape}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    tick = paged.decode_tick(cfg, nb * BS, "pallas", fmt,
                             interpret=False).lower(
        params, arena, dense, i32(lanes, nb), i32(lanes, 1),
        jax.ShapeDtypeStruct((lanes,), bool, sharding=one_chip),
        i32(lanes)).compile()
    return tick, shape


def test_served_latent_tick_reads_the_arena_where_it_lies(one_chip):
    """Moonlight's served tick at the benchmark's cut (9 layers, 64 lanes,
    8704 blocks): the latent arena is held row-major and read and written
    by the kernels where it lies, with no copy or slice of the whole arena
    or of one layer of it and no arena-sized temporary (the arena is
    1.44e9 B)."""
    cfg = dataclasses.replace(configs.config("moonshot_v1_16b_a3b"),
                              n_layers=MLA_LAYERS)
    tick, shape = _served_tick(one_chip, cfg, MLA_LANES, MLA_NB, MLA_BLOCKS)
    text = tick.as_text()
    assert "latent_decode_attention" in text
    assert "scatter_latent_rows" in text
    assert _arena_sized_ops(text, shape["latent"].shape) == []
    assert tick.memory_analysis().temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("arch", ["stablelm_3b", "hymba_1_5b"])
def test_served_tick_reads_the_arena_where_it_lies(one_chip, arch):
    """The full-width Pallas tick, built as the adapter builds it
    (``paged.arena_formats`` + ``paged.decode_tick``) from abstract
    params: with the arena held row-major and handed whole to the
    kernels, no copy or slice of the whole arena or of one layer of it
    remains (head dims 80 and 64 both sit off the 128 lanes), and the
    compiler's temporaries stay far below an arena (3.82e9 B for
    stablelm-3b with the default layout and per-layer slices)."""
    tick, shape = _served_tick(one_chip, configs.config(arch), TICK_LANES,
                               TICK_NB, TICK_BLOCKS)
    text = tick.as_text()
    assert "tpu_custom_call" in text
    assert _arena_sized_ops(text, shape["k"].shape) == []
    assert tick.memory_analysis().temp_size_in_bytes < 0.5e9

