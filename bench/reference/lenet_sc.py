"""Plain reference of the paper's hybrid LeNet-5 (Fig. 3), frame by frame.

First layer: the stochastic-computing engine of section IV.B, simulated
at stream level.  Each 8-bit pixel becomes a level ``round(x/255 * N)``
(``N = 2**bits``) and a thermometer stream (ramp compare); each weight of
a 5x5 kernel, scaled so the kernel's largest magnitude is 1, becomes a
positive and a negative level and a low-discrepancy stream (bit-reversed
Gray counter).  A product is the popcount of the AND of two streams.  The
25 products of a window go through a tree of toggle-flip-flop adders
(padded to 32 leaves), where a node outputs ``(left + right + s0) >> 1``
with ``s0 = (node + level) & 1``.  The activation is the sign of the
positive minus the negative root count.  Then a 2x2 max-pool, and the
binary tail (conv 5x5 SAME + ReLU, max-pool, dense + ReLU, dense) in
float32 at ``precision=HIGHEST``.

``class_logits`` returns the reference logits of a set of images and,
given the name of one of ``CONTROLS``, those of that control: the
reference one precision step below the configuration, which the driver
then puts in the program's place.  ``short_streams`` runs the SC layer
on streams one bit shorter (``bits - 1``; the program has this path,
``FrontendSpec.bits``); ``tail_f8`` rounds every product's operands in
the tail to float8 (e4m3, scaled per row and per output column), the
step below the bfloat16 products a TPU makes of the float32 tail at its
default precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.reference.decoder import F8, F8_MAX

HI = jax.lax.Precision.HIGHEST
CONTROLS = ("short_streams", "tail_f8")


def product_table(bits: int) -> np.ndarray:
    """popcount(ramp stream of a AND Gray-LD stream of b), all levels."""
    n = 1 << bits
    t = np.arange(n)
    gray = t ^ (t >> 1)
    rev = np.zeros(n, np.int64)
    for i in range(bits):
        rev |= ((gray >> i) & 1) << (bits - 1 - i)
    lv = np.arange(n + 1)
    a = t[None, :] < lv[:, None]             # ramp compare: (level, t)
    b = rev[None, :] < lv[:, None]           # low-discrepancy compare
    return (a[:, None, :] & b[None, :, :]).sum(-1).astype(np.int32)


def _tff_tree(counts):
    """(..., K) leaf counts -> root count of the TFF adder tree."""
    k = counts.shape[-1]
    depth = max(1, int(np.ceil(np.log2(max(k, 2)))))
    c = jnp.pad(counts, [(0, 0)] * (counts.ndim - 1) + [(0, (1 << depth) - k)])
    for level in range(depth):
        node = jnp.arange(c.shape[-1] // 2)
        c = (c[..., 0::2] + c[..., 1::2] + ((node + level) & 1)) >> 1
    return c[..., 0]


def _windows(x, k):
    """(B, H, W) -> (B, H, W, k*k) SAME-padded windows, row-major."""
    p = k // 2
    xp = jnp.pad(x, ((0, 0), (p, p), (p, p)))
    h, w = x.shape[1:]
    return jnp.stack([xp[:, i:i + h, j:j + w] for i in range(k)
                      for j in range(k)], -1)


def sc_features(c: dict, sc, w1, images_u8) -> jax.Array:
    """(B, 28, 28, 1) uint8 -> pooled ternary features (B, 14, 14, C1)."""
    bits = dict(sc)["bits"]
    n = 1 << bits
    table = jnp.asarray(product_table(bits))
    x = images_u8[..., 0].astype(jnp.float32) / 255.0
    lv = jnp.clip(jnp.round(x * n), 0, n).astype(jnp.int32)
    win = _windows(lv, c["ksize"])                         # (B, H, W, K)
    w = w1.reshape(-1, w1.shape[-1])                       # (K, C1)
    wn = w / jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-8)
    pos = jnp.clip(jnp.round(jnp.maximum(wn, 0) * n), 0, n).astype(jnp.int32)
    neg = jnp.clip(jnp.round(jnp.maximum(-wn, 0) * n), 0, n).astype(jnp.int32)

    def root(wl):
        prod = table[win[..., None, :], wl.T]              # (B,H,W,C1,K)
        return _tff_tree(prod)
    h1 = jnp.sign(root(pos) - root(neg)).astype(jnp.float32)
    return _pool(h1)


def _pool(x):
    b, h, w, ch = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, ch).max(axis=(2, 4))


def _f8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def tail(p, h, low: bool):
    """Binary-domain remainder on pooled features (B, 14, 14, C1)."""
    w2 = p["conv2"]["w"]
    if low:
        h, w2 = _f8(h, -1), _f8(w2, (0, 1, 2))
    h = jax.lax.conv_general_dilated(
        h, w2, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HI) + p["conv2"]["b"]
    h = _pool(jax.nn.relu(h)).reshape(h.shape[0], -1)
    for name, act in (("dense1", jax.nn.relu), ("dense2", lambda v: v)):
        a, w = (h, p[name]["w"])
        if low:
            a, w = _f8(a, -1), _f8(w, 0)
        h = act(jnp.dot(a, w, precision=HI) + p[name]["b"])
    return h


def class_logits(c: dict, sc: dict, seed: int, images_u8: np.ndarray,
                 control: str | None = None, block: int = 32) -> dict:
    """Reference logits per image (``"logits"``) and, with ``control``,
    the control's (``"control"``)."""
    if sc["scheme"] != "ramp_lowdisc" or sc["adder"] != "tff" or \
            sc["s0_mode"] != "alt" or not sc["weight_scale"] or \
            sc["soft_threshold"] != 0.0:
        raise ValueError("this reference covers the paper's ramp/LD "
                         "streams, alternating TFF tree, scaled weights")
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}; have {CONTROLS}")
    p = weights.lenet_params(c, seed)
    feats = jax.jit(lambda w1, x, s: sc_features(c, s, w1, x),
                    static_argnums=(2,))
    run = jax.jit(tail, static_argnums=(2,))
    full = tuple(sc.items())
    short = tuple(dict(sc, bits=sc["bits"] - 1).items())
    out = {"logits": [], "control": []}
    for i in range(0, len(images_u8), block):
        x = jnp.asarray(images_u8[i:i + block])
        h = feats(p["conv1"]["w"], x, full)
        out["logits"].append(np.asarray(run(p, h, False)))
        if control == "tail_f8":
            out["control"].append(np.asarray(run(p, h, True)))
        elif control == "short_streams":
            out["control"].append(
                np.asarray(run(p, feats(p["conv1"]["w"], x, short), False)))
    return {k: np.concatenate(v) for k, v in out.items() if v}
