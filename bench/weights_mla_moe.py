"""Random weights from ``--seed`` for the latent-attention MoE family
(Moonlight-16B-A3B), made by the benchmark in the layout the program serves
(``models/lm.py``, family ``moe`` with ``kv_lora_rank > 0``) and the plain
reference (``bench/reference/mla_moe.py``) reads.

As in ``bench/weights.py``: every leaf draws from its own key,
``fold_in(base_key(seed), leaf)``, and every layer of it from
``fold_in(that, layer)``, with ``layer`` the model's layer number (0 is
the dense layer, 1.. the MoE layers).  So the reference makes one layer
alone and gets exactly the values the program was given.  The served
pytree is made one layer at a time inside one program (``lax.map``), so
the device holds the weights and one layer's float32 draw, never a second
copy of the model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights

TOP = ("embed", "lm_head")
ATTN = ("wq", "w_kv_a", "w_kv_b", "wo")
DENSE = ("mlp.w_gate", "mlp.w_in", "mlp.w_out")
MOE = ("w_router", "router_bias", "w_gate", "w_in", "w_out",
       "shared_gate", "shared_in", "shared_out")
_LEAF_ID = {name: 100 + i for i, name in enumerate(TOP + ATTN + DENSE + MOE)}
# standard deviation of the router's correction bias (assumed: the
# published model's trained values are not part of its config)
BIAS_SCALE = 0.05


def shapes(m: dict) -> dict:
    """Shapes of every random leaf: top-level ones and one layer's."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    E, f, f0 = m["n_experts"], m["d_expert"], m["first_dense_ff"]
    sf = m["n_shared"] * f
    vp = -(-m["vocab"] // 128) * 128
    return {"embed": (vp, d), "lm_head": (d, vp),
            "wq": (d, H * (dn + dr)), "w_kv_a": (d, r + dr),
            "w_kv_b": (r, H * (dn + dv)), "wo": (H * dv, d),
            "mlp.w_gate": (d, f0), "mlp.w_in": (d, f0), "mlp.w_out": (f0, d),
            "w_router": (d, E), "router_bias": (E,),
            "w_gate": (E, d, f), "w_in": (E, d, f), "w_out": (E, f, d),
            "shared_gate": (d, sf), "shared_in": (d, sf),
            "shared_out": (sf, d)}


def leaf(m: dict, root, name: str, layer=None):
    """One leaf (or one layer of a stacked leaf) in the served dtype, from
    the run's root key (``weights.base_key(seed)``); the router's bias is
    float32, as the program holds it."""
    shape = shapes(m)[name]
    key = jax.random.fold_in(root, _LEAF_ID[name])
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    if name == "router_bias":
        return weights._draw(key, shape, BIAS_SCALE, jnp.float32)
    scale = 0.02 if name == "embed" else shape[-2] ** -0.5
    return weights._draw(key, shape, scale, jnp.dtype(m["param_dtype"]))


def params(m: dict, seed: int) -> dict:
    """The whole served pytree, made on the device in one program."""
    L, d, r = m["n_layers"], m["d_model"], m["kv_lora_rank"]
    dtype = jnp.dtype(m["param_dtype"])

    def make(root):
        def stack(name, lo, hi):
            return jax.lax.map(lambda l: leaf(m, root, name, l),
                               jnp.arange(lo, hi))

        def block(lo, hi):
            ones = lambda w: jnp.ones((hi - lo, w), dtype)
            attn = {n: stack(n, lo, hi) for n in ATTN}
            attn["kv_norm"] = {"scale": ones(r)}
            return {"ln1": {"scale": ones(d)}, "attn": attn,
                    "ln2": {"scale": ones(d)}}
        dense0 = dict(block(0, 1), mlp={n.split(".")[1]: stack(n, 0, 1)
                                        for n in DENSE})
        blocks = dict(block(1, L), moe={n: stack(n, 1, L) for n in MOE})
        return {"embed": leaf(m, root, "embed"),
                "lm_head": leaf(m, root, "lm_head"),
                "final_norm": {"scale": jnp.ones((d,), dtype)},
                "dense0": dense0, "blocks": blocks}
    return jax.block_until_ready(jax.jit(make)(weights.base_key(seed)))
