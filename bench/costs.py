"""Operations and bytes the algorithms need, counted from shapes.

These are the numerators of every roofline share and MFU the benchmark
reports.  They count what the mathematics needs at the live sizes (the
context a lane really holds, the frames really classified), never what an
implementation happens to sweep, so the same count holds whatever kernel
does the work.  ``peak(kind)`` reads ``peaks.json``; a device kind that is
not there is an error.
"""
from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent


def peak(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer's matrix products."""
    d, f = m["d_model"], m["d_ff"]
    q = m["n_heads"] * m["d_head"]
    kv = m["n_kv_heads"] * m["d_head"]
    ff = 3 if m["mlp_type"] == "swiglu" else 2
    return d * q + 2 * d * kv + q * d + ff * d * f


def head_params(m: dict) -> int:
    return m["d_model"] * (-(-m["vocab"] // 128) * 128)


def attn_flops(m: dict, rows: int) -> int:
    """One query against ``rows`` keys in every layer: QK^T and PV."""
    return 4 * m["n_heads"] * m["d_head"] * rows * m["n_layers"]


def decode_flops(m: dict, lens) -> int:
    """One decode tick over lanes holding ``lens`` tokens each (the new
    token attends ``len + 1`` rows)."""
    per_token = 2 * (m["n_layers"] * layer_matmul_params(m) + head_params(m))
    return sum(per_token + attn_flops(m, int(n) + 1) for n in lens)


def prefill_flops(m: dict, start: int, count: int) -> int:
    """Folding prompt positions ``start .. start+count-1`` in chunks of
    ``block_size``, each chunk ending in one row of the output head."""
    dense = 2 * m["n_layers"] * layer_matmul_params(m) * count
    att = sum(attn_flops(m, p + 1) for p in range(start, start + count))
    return dense + att


def prefill_head_flops(m: dict, chunks: int) -> int:
    return 2 * head_params(m) * chunks


def paged_attn_cost(m: dict, lens) -> tuple[int, int]:
    """(flops, bytes) the paged decode attention needs in one tick: every
    layer reads the K and V rows of each lane's live context (``len``
    rows from the cache plus the new one) and its query, and writes its
    output."""
    item = _itemsize(m["param_dtype"])
    kv_row = 2 * m["n_kv_heads"] * m["d_head"] * item
    q_row = m["n_heads"] * m["d_head"] * item
    rows = sum(int(n) + 1 for n in lens)
    nbytes = m["n_layers"] * (rows * kv_row + 2 * q_row * len(lens))
    return attn_flops(m, rows), nbytes


def lenet_macs(c: dict) -> int:
    """Multiply-accumulates of one frame, the SC first layer counted once
    at its binary equivalent (one k*k*C window per output)."""
    s, k, ch = c["image_size"], c["ksize"], c["channels"]
    c1, c2 = c["conv1_filters"], c["conv2_filters"]
    conv1 = s * s * k * k * ch * c1
    conv2 = (s // 2) ** 2 * k * k * c1 * c2
    flat = (s // 4) ** 2 * c2
    return conv1 + conv2 + flat * c["dense"] + c["dense"] * c["classes"]
