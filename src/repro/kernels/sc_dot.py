"""Pallas TPU kernel: bit-packed stochastic dot product (AND + popcount + TFF
tree), the compute hot-spot of the paper's 784-unit convolution engine.

TPU adaptation (see DESIGN.md §2): the ASIC's serial AND-gates + TFF adder
tree become, per grid cell, a word-parallel AND over packed uint32 streams,
a SWAR popcount (shift/mask adds only — no reliance on a native
population-count lowering), and an integer TFF-tree reduction in VMEM.

Tiling: grid (M/bm, O/bo); each cell loads
    X tile (K, bm, Wd)  and  W tile (K, Wd, bo)
into VMEM and emits a (bm, bo) int32 tile of root counts.  K (window size,
padded to a power of two by the wrapper) and Wd (words per stream, N/32) are
small — e.g. K=32, Wd=8 at 8-bit precision.  K leads both tiles so the leaf
loop indexes an untiled axis; word d of every stream is then a static
column (bm, 1) of X and row (1, bo) of W, whose AND broadcasts to the
(bm, bo) tile.  Words travel as int32 bit patterns (Mosaic has no unsigned
reductions).  The working set, with Wd padded to a 128-lane row and to an
8-row sublane group, is
  K*bm*128*4 + K*8*bo*4 + K*bm*bo*4 bytes;
with bm=bo=128, K=32: 2MiB + 128KiB + 2MiB ≈ 4.1MiB < 16MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _swar_popcount(v: jax.Array) -> jax.Array:
    """Branch-free popcount of a 32-bit word held as int32, using shift/mask
    adds only (VPU-friendly).  Logical shifts and wrapping int32 arithmetic
    give the same bits as the uint32 form, which Mosaic cannot reduce."""
    srl = jax.lax.shift_right_logical
    v = v - (srl(v, 1) & jnp.int32(0x55555555))
    v = (v & jnp.int32(0x33333333)) + (srl(v, 2) & jnp.int32(0x33333333))
    v = (v + srl(v, 4)) & jnp.int32(0x0F0F0F0F)
    return srl(v * jnp.int32(0x01010101), 24)


def _tree_reduce(counts: jax.Array, s0_mode: str) -> jax.Array:
    """TFF adder tree over axis 0 of (K, bm, bo) int32 -> (bm, bo)."""
    K = counts.shape[0]
    depth = int(np.log2(K))
    c = counts
    for level in range(depth):
        half = c.shape[0] // 2
        c2 = c.reshape((half, 2) + c.shape[1:])
        left, right = c2[:, 0], c2[:, 1]
        if s0_mode == "zero":
            s0 = 0
        elif s0_mode == "one":
            s0 = 1
        else:  # "alt"
            idx = jax.lax.broadcasted_iota(jnp.int32, (half, 1, 1), 0)
            s0 = (idx + level) & 1
        c = (left + right + s0) >> 1
    return c[0]


def _sc_dot_kernel(x_ref, w_ref, o_ref, counts_scr, *, s0_mode: str,
                   adder: str):
    """x_ref: (K, bm, Wd) i32; w_ref: (K, Wd, bo) i32; o_ref: (bm, bo) i32;
    counts_scr: (K, bm, bo) i32 — one popcount tile per tree leaf."""
    K, _, Wd = x_ref.shape

    def body(k, carry):
        xk = x_ref[k]                                   # (bm, Wd)
        wk = w_ref[k]                                   # (Wd, bo)
        cnt = _swar_popcount(xk[:, 0:1] & wk[0:1, :])   # (bm, bo)
        for d in range(1, Wd):
            cnt = cnt + _swar_popcount(xk[:, d:d + 1] & wk[d:d + 1, :])
        counts_scr[k] = cnt
        return carry

    jax.lax.fori_loop(0, K, body, 0)
    counts = counts_scr[...]
    if adder == "ideal":
        depth = int(np.log2(K))
        o_ref[...] = jnp.sum(counts, axis=0) >> depth
    else:
        o_ref[...] = _tree_reduce(counts, s0_mode)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bo", "s0_mode", "adder", "interpret"))
def sc_dot_pallas(x_packed: jax.Array, w_packed: jax.Array, *,
                  bm: int = 128, bo: int = 128, s0_mode: str = "alt",
                  adder: str = "tff",
                  interpret: bool | None = None) -> jax.Array:
    """Raw pallas_call (operands must already be padded to block multiples
    and K padded to a power of two).  Use :mod:`repro.kernels.ops` instead.
    ``interpret=None`` auto-detects the backend (Mosaic on TPU only).
    """
    from repro.kernels.ops import resolve_interpret   # deferred: ops imports us
    interpret = resolve_interpret(interpret)
    M, K, Wd = x_packed.shape
    K2, O, Wd2 = w_packed.shape
    assert K == K2 and Wd == Wd2 and M % bm == 0 and O % bo == 0
    assert K & (K - 1) == 0, "K must be padded to a power of two"

    # stream words as int32 bit patterns, K leading so the leaf loop
    # indexes an untiled axis: X (K, M, Wd), W (K, Wd, O)
    x = jax.lax.bitcast_convert_type(x_packed, jnp.int32).transpose(1, 0, 2)
    w = jax.lax.bitcast_convert_type(w_packed, jnp.int32).transpose(0, 2, 1)
    grid = (M // bm, O // bo)
    return pl.pallas_call(
        functools.partial(_sc_dot_kernel, s0_mode=s0_mode, adder=adder),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, bm, Wd), lambda i, j: (0, i, 0)),
            pl.BlockSpec((K, Wd, bo), lambda i, j: (0, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bo), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, O), jnp.int32),
        scratch_shapes=[pltpu.VMEM((K, bm, bo), jnp.int32)],
        interpret=interpret,
    )(x, w)
