"""Plain float32 reference of the decoder family as the program serves it.

Pre-norm blocks of RMSNorm, causal multi-head attention with rotary
embedding over the whole head (rotate-half form), SwiGLU feed-forward, a
final RMSNorm and an untied output head.  Plain ``jax.numpy`` at
``precision=HIGHEST``, no cache, no kernels, no batching of lanes: the whole
sequence in one pass, layer by layer, with each layer's weights made anew
from the seed (``bench/weights.py``) so that a 2.8B-parameter model never
sits on the device in float32.

``served_gaps`` is the comparison that decides ``correct`` in the LM
cells: for every token the program served, how far the reference's logit
of that token lies below the reference's best logit at that position.
The one control, ``f8``, also runs the same pass with every matrix
product's operands rounded to float8 (e4m3, scaled per row of
activations and per output column of weights): the precision step below
the bfloat16 the configuration states, read at the same prompts and
tokens, where the driver puts it in the program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
CONTROLS = ("f8",)


def _f8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, w, low: bool):
    if low:
        a, w = _f8(a, -1), _f8(w, 0)
    return jnp.einsum("...d,df->...f", a, w, precision=HI)


def _rms(x, eps):
    # every norm scale the benchmark makes is 1
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """x: (N, S, H, D) at positions 0..S-1."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m, w, x, low: bool):
    n, s, _ = x.shape
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    h = _rms(x, m["norm_eps"])
    q = _rope(_mm(h, w["wq"], low).reshape(n, s, hq, dh), m["rope_theta"])
    k = _rope(_mm(h, w["wk"], low).reshape(n, s, hkv, dh), m["rope_theta"])
    v = _mm(h, w["wv"], low).reshape(n, s, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    att = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", att, v, precision=HI)
    x = x + _mm(o.reshape(n, s, hq * dh), w["wo"], low)
    h = _rms(x, m["norm_eps"])
    g = _mm(h, w["w_gate"], low)
    return x + _mm(jax.nn.silu(g) * _mm(h, w["w_in"], low), w["w_out"], low)


def _round_up(n: int, q: int) -> int:
    return -(-n // q) * q


def served_gaps(m: dict, seed: int, served: list[tuple[np.ndarray, list]],
                control: str | None = None) -> dict:
    """``served``: (prompt, generated tokens) per request.  Returns the gap
    of every served token (``"gaps"``) and, with ``control="f8"``, the gap
    of the token the float8 pass puts first at each of those positions
    (``"control_gaps"``).

    Requests are padded to a multiple of 4 and sequences to a multiple of
    256 positions, and the seed's key is an argument of every program, so
    the programs compile once per cell and then come from the cache."""
    if m["family"] != "decoder" or m["mlp_type"] != "swiglu" or \
            m["norm_type"] != "rmsnorm" or m["tie_embeddings"]:
        raise ValueError("this reference covers untied RMSNorm/SwiGLU "
                         "decoders only")
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control {control!r}; have {CONTROLS}")
    control = control is not None
    seqs = [np.concatenate([np.asarray(p), np.asarray(g[:-1], np.int32)])
            for p, g in served]
    n, s = _round_up(len(seqs), 4), _round_up(max(len(t) for t in seqs), 256)
    tokens = np.zeros((n, s), np.int32)
    rows = np.zeros(n * s, np.int32)
    cols = np.zeros(n * s, np.int32)
    want = np.zeros(n * s, np.int32)
    k = 0
    for i, ((p, g), t) in enumerate(zip(served, seqs)):
        tokens[i, :len(t)] = t
        for j, tok in enumerate(g):
            rows[k], cols[k], want[k] = i, len(p) - 1 + j, int(tok)
            k += 1
    root = weights.base_key(seed)
    x = jax.jit(lambda key, t: weights.decoder_leaf(m, key, "embed")
                .astype(jnp.float32)[t])(root, jnp.asarray(tokens))
    layer_w = jax.jit(lambda key, l: {
        name: weights.decoder_leaf(m, key, name, l).astype(jnp.float32)
        for name in weights.DECODER_LAYER})
    step = jax.jit(lambda w, xi, low: _layer(m, w, xi, low),
                   static_argnums=(2,))
    xs = [x, x] if control else [x]
    for layer in range(m["n_layers"]):
        w = layer_w(root, layer)
        xs = [step(w, xi, low) for xi, low in zip(xs, (False, True))]

    @jax.jit
    def gaps(key, xr, xc, rows, cols, want):
        head = weights.decoder_leaf(m, key, "lm_head").astype(jnp.float32)
        ref = _mm(_rms(xr[rows, cols], m["norm_eps"]), head, False)
        best = jnp.max(ref, -1)
        at = jnp.arange(rows.shape[0])
        out = [best - ref[at, want]]
        if xc is not None:
            pick = jnp.argmax(_mm(_rms(xc[rows, cols], m["norm_eps"]), head,
                                  True), -1)
            out.append(best - ref[at, pick])
        return out
    got = gaps(root, xs[0], xs[1] if control else None, jnp.asarray(rows),
               jnp.asarray(cols), jnp.asarray(want))
    out = {"gaps": np.asarray(got[0])[:k]}
    if control:
        out["control_gaps"] = np.asarray(got[1])[:k]
    return out
