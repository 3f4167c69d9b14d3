"""Pallas TPU kernel: comparator SNG + bit-packing.

Generates packed stochastic streams from integer levels on-chip, so the
HBM->VMEM traffic is ``4 bytes/level`` in and ``N/8 bytes/stream`` out with no
intermediate (N,)-bool materialization in HBM.  The comparator's code
sequence (ramp / van-der-Corput / reversed-Gray / LFSR) is a small constant
(N int32 = 1KiB at 8-bit) broadcast to every grid cell.

Per grid cell: a levels column (blk, 1) int32 and the codes row (1, N)
int32 produce a (blk, N/32) tile: bit t of word w = (codes[32w+t] < level).
Each word is the sum of its 32 distinct powers of two, taken in int32 (Mosaic
has no unsigned reductions; with no carries the bits are the uint32 word's),
and the wrapper reinterprets the tile as uint32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sng_pack_kernel(lvl_ref, codes_ref, out_ref):
    lvl = lvl_ref[...]                       # (blk, 1)
    codes = codes_ref[...]                   # (1, length)
    blk, nw = out_ref.shape
    t = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1)
    # bit t of the whole stream, weighted into its word: 2**(t % 32)
    bits = (codes < lvl).astype(jnp.int32) << (t & 31)        # (blk, length)
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, nw), 1)
    out = jnp.zeros((blk, nw), jnp.int32)
    for w in range(nw):
        word = jnp.sum(jnp.where((t >> 5) == w, bits, 0), axis=-1,
                       keepdims=True)                          # (blk, 1)
        out = jnp.where(col == w, word, out)
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("length", "block", "interpret"))
def sng_pack_pallas(levels: jax.Array, codes: jax.Array, *, length: int,
                    block: int = 256,
                    interpret: bool | None = None) -> jax.Array:
    """levels: (M,) int32 (M % block == 0); codes: (length,) int32.
    Returns (M, length//32) uint32 packed streams.
    ``interpret=None`` auto-detects the backend (Mosaic on TPU only)."""
    from repro.kernels.ops import resolve_interpret   # deferred: ops imports us
    interpret = resolve_interpret(interpret)
    M = levels.shape[0]
    assert M % block == 0
    nw = length // 32
    out = pl.pallas_call(
        _sng_pack_kernel,
        grid=(M // block,),
        in_specs=[
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, length), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block, nw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, nw), jnp.int32),
        interpret=interpret,
    )(levels.reshape(M, 1), codes.reshape(1, length))
    return jax.lax.bitcast_convert_type(out, jnp.uint32)
