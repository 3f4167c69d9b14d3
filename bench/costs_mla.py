"""Operations and bytes that latent-attention MoE decoding needs, counted
from shapes at the live lengths (Moonlight-16B-A3B; ``bench/costs.py``
holds the dense decoder's).

As there, these count what the mathematics needs, never what an
implementation sweeps: the active parameters of each token (the attention
projections, the router, ``top_k`` routed and the shared experts, layer
0's dense feed-forward, the output head) and latent attention in its
absorbed form over the rows each lane really holds.  ``m`` is the
configuration's ``model`` group.
"""
from __future__ import annotations


def _item(m: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[m["param_dtype"]]


def attn_params(m: dict) -> int:
    """Weights of one layer's latent-attention projections."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def token_params(m: dict) -> int:
    """Weights one token multiplies on its way through every layer and the
    head: layer 0 dense, the rest ``top_k`` routed plus the shared
    experts and the router."""
    d, f = m["d_model"], m["d_expert"]
    dense = attn_params(m) + 3 * d * m["first_dense_ff"]
    moe = attn_params(m) + d * m["n_experts"] + \
        (m["top_k"] + m["n_shared"]) * 3 * d * f
    head = d * (-(-m["vocab"] // 128) * 128)
    return dense + (m["n_layers"] - 1) * moe + head


def latent_row_flops(m: dict) -> int:
    """One query against one cached latent row, every head: the score
    over the latent and RoPE lanes, and the value product over the
    latent."""
    H, r, dr = m["n_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    return 2 * H * (2 * r + dr)


def decode_flops(m: dict, lens) -> int:
    """One decode tick over lanes holding ``lens`` tokens each (the new
    token attends ``len + 1`` rows in every layer)."""
    per_row = m["n_layers"] * latent_row_flops(m)
    return sum(2 * token_params(m) + per_row * (int(n) + 1) for n in lens)


def prefill_flops(m: dict, start: int, count: int) -> int:
    """Folding prompt positions ``start .. start+count-1``: the active
    parameters of each token but the head, and attention in its expanded
    form (scores over nope + rope lanes, values) against every earlier
    position."""
    H = m["n_heads"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    head = m["d_model"] * (-(-m["vocab"] // 128) * 128)
    per_row = m["n_layers"] * 2 * H * (dn + dr + dv)
    rows = sum(p + 1 for p in range(start, start + count))
    return 2 * (token_params(m) - head) * count + per_row * rows


def prefill_head_flops(m: dict, chunks: int) -> int:
    """One output-head row per fold chunk."""
    return 2 * m["d_model"] * (-(-m["vocab"] // 128) * 128) * chunks


def latent_attn_cost(m: dict, lens) -> tuple[int, int]:
    """(flops, bytes) the latent decode read needs in one tick: every layer
    reads one latent row per live position of each lane (``len`` cached
    rows plus the new one) and the lane's absorbed query, and writes its
    output over the latent."""
    H, r, dr = m["n_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    item = _item(m)
    rows = sum(int(n) + 1 for n in lens)
    nbytes = m["n_layers"] * item * (rows * (r + dr)
                                     + len(lens) * H * (2 * r + dr))
    return m["n_layers"] * latent_row_flops(m) * rows, nbytes
