"""Random weights from ``--seed``, made by the benchmark in the layout the
program serves and the plain references read.

Every leaf draws from its own key, ``fold_in(base_key(seed), leaf)``, and every
layer of a stacked leaf from ``fold_in(that, layer)``.  So a reference can
make one layer alone and get exactly the values the program was given,
without keeping or reading anything the program holds.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

DECODER_TOP = ("embed", "lm_head")
DECODER_LAYER = ("wq", "wk", "wv", "wo", "w_gate", "w_in", "w_out")
_LEAF_ID = {name: i for i, name in
            enumerate(DECODER_TOP + DECODER_LAYER + ("lenet",))}


def decoder_shapes(m: dict) -> dict:
    """Shapes of every random leaf: top-level ones and one layer's."""
    d, f = m["d_model"], m["d_ff"]
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    vp = -(-m["vocab"] // 128) * 128
    return {"embed": (vp, d), "lm_head": (d, vp),
            "wq": (d, hq * dh), "wk": (d, hkv * dh), "wv": (d, hkv * dh),
            "wo": (hq * dh, d), "w_gate": (d, f), "w_in": (d, f),
            "w_out": (f, d)}


def base_key(seed: int):
    """The run's root key; every bit of a seed of up to 64 bits counts."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _draw(key, shape, scale, dtype):
    """Truncated normal on (-2, 2) times ``scale``, rounded to ``dtype``."""
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return (scale * x).astype(dtype)


def _scale(name: str, shape) -> float:
    return 0.02 if name == "embed" else 1.0 / math.sqrt(shape[-2])


def decoder_leaf(m: dict, root, name: str, layer=None):
    """One leaf (or one layer of a stacked leaf) in the served dtype, from
    the run's root key (``base_key(seed)``)."""
    shape = decoder_shapes(m)[name]
    key = jax.random.fold_in(root, _LEAF_ID[name])
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    return _draw(key, shape, _scale(name, shape), jnp.dtype(m["param_dtype"]))


def decoder_params(m: dict, seed: int) -> dict:
    """The whole served pytree, made on the device in one program."""
    L, d = m["n_layers"], m["d_model"]
    dtype = jnp.dtype(m["param_dtype"])

    def make(base):
        def leaf(name):
            shape = decoder_shapes(m)[name]
            key = jax.random.fold_in(base, _LEAF_ID[name])
            if name in DECODER_TOP:
                return _draw(key, shape, _scale(name, shape), dtype)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(L))
            return jax.vmap(lambda kk: _draw(kk, shape, _scale(name, shape),
                                             dtype))(keys)
        ones = jnp.ones((L, d), dtype)
        return {
            "embed": leaf("embed"), "lm_head": leaf("lm_head"),
            "final_norm": {"scale": jnp.ones((d,), dtype)},
            "blocks": {
                "ln1": {"scale": ones}, "ln2": {"scale": ones},
                "attn": {n: leaf(n) for n in ("wq", "wk", "wv", "wo")},
                "mlp": {n: leaf(n) for n in ("w_gate", "w_in", "w_out")}}}
    return jax.block_until_ready(jax.jit(make)(base_key(seed)))


def lenet_params(c: dict, seed: int) -> dict:
    """LeNet-5 weights (float32, He-normal, zero biases) from ``seed``."""
    ks, c1, c2 = c["ksize"], c["conv1_filters"], c["conv2_filters"]
    flat = (c["image_size"] // 4) ** 2 * c2
    shapes = {"conv1": (ks, ks, c["channels"], c1), "conv2": (ks, ks, c1, c2),
              "dense1": (flat, c["dense"]), "dense2": (c["dense"],
                                                       c["classes"])}

    def make(root):
        base = jax.random.fold_in(root, _LEAF_ID["lenet"])
        he = jax.nn.initializers.he_normal()
        return {name: {"w": he(jax.random.fold_in(base, i), shape,
                               jnp.float32),
                       "b": jnp.zeros((shape[-1],), jnp.float32)}
                for i, (name, shape) in enumerate(shapes.items())}
    return jax.block_until_ready(jax.jit(make)(base_key(seed)))
