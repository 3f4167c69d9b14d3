"""Unified LM model zoo: one config + one param/spec/forward factory for all
ten assigned architectures.

Families
  decoder : llama3-405b, starcoder2-15b, deepseek-67b, stablelm-3b
  moe     : deepseek-moe-16b (dense layer 0 + MoE rest; MHA, softmax router),
            moonshot-v1-16b-a3b = Moonlight-16B-A3B (dense layer 0 + MoE rest;
            latent attention (MLA), biased sigmoid router)
  rwkv    : rwkv6-7b (attention-free; time-mix + channel-mix)
  hybrid  : hymba-1.5b (parallel GQA + Mamba heads, sliding window + globals)
  encdec  : whisper-medium (frame-embedding encoder + causal/cross decoder)
  vlm     : llama-3.2-vision-90b (decoder + gated cross-attn every 5th layer)

Conventions
  - Params are plain pytrees (dicts of jnp arrays); per-layer params are
    stacked with a leading layer axis and consumed by ``lax.scan`` (compact
    HLO at 126 layers, per-layer remat).
  - ``init(key, cfg, mesh_shape)`` returns ``(params, specs)`` — mirrored
    pytrees.  ``abstract=True`` returns ShapeDtypeStructs instead of arrays
    (no allocation — how the 405B dry-run builds its inputs).
  - Sharding: TP on "model", FSDP on "data", with automatic fallback to
    replication when a dim is not divisible by the mesh axis.
  - Modality frontends (whisper audio conv, VLM image tower) are STUBS per
    the assignment: batches carry precomputed frame/patch embeddings.
  - Serve caches: attention K/V are (L, B, Smax, Hkv, Dh); latent attention
    caches one (L, B, Smax, kv_lora_rank + qk_rope_head_dim) row per token;
    RWKV/Mamba carry O(1) recurrent state — which is why only those families
    run long_500k.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import hint
from repro.nn import attention, mlp as mlp_lib, norms, rope, ssm
from repro.nn.moe import MoEConfig, expert_rows, moe_ffn

DATA, MODEL = "data", "model"   # logical mesh axis names (pod handled by batch)


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str = "lm"
    family: str = "decoder"
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_head: int = 64
    d_ff: int = 1024
    vocab: int = 1024
    mlp_type: str = "swiglu"          # "swiglu" | "gelu"
    use_bias: bool = False            # whisper-style biases
    rope_theta: float = 500000.0
    pos_embedding: str = "rope"       # "rope" | "sinusoidal"
    norm_type: str = "rmsnorm"        # "rmsnorm" | "layernorm"
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # --- moe ---
    n_experts: int = 0
    top_k: int = 0
    n_shared: int = 0
    d_expert: int = 0
    first_dense_ff: int = 0           # layer-0 dense FFN width (moe family)
    moe_group_size: int = 2048
    moe_impl: str = "einsum"
    capacity_factor: float = 1.25
    # serving prefill routes dropless (see decoder_block): required for
    # prefix-cache resumption; off by default so the training forward and
    # the dry-run roofline cells keep GShard capacity semantics
    moe_dropless_prefill: bool = False
    router_score: str = "softmax"     # "softmax" | "sigmoid" (see nn/moe.py)
    routed_scaling: float = 1.0       # sigmoid router's output scale
    # --- latent attention (MLA, DeepSeek-V2/V3 form without a query LoRA);
    # on when kv_lora_rank > 0.  Queries are n_heads x (nope + rope) wide,
    # the cache holds the RMS-normed kv_lora_rank latent and one RoPE'd
    # qk_rope_head_dim key part shared by every head.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- vlm ---
    cross_every: int = 0              # a cross-attn layer every k layers
    n_vision_tokens: int = 1024
    # --- encdec ---
    enc_layers: int = 0
    enc_len: int = 1500
    # --- hybrid / ssm ---
    ssm_state: int = 0
    d_inner: int = 0                  # mamba inner width (2*d_model default)
    dt_rank: int = 0
    conv_k: int = 4
    window: int = 0                   # sliding-window size (0 = full attn)
    global_every: int = 0             # every k-th layer is full attention
    # --- numerics / runtime ---
    param_dtype: str = "bfloat16"
    remat: str = "full"               # "none" | "full" | "dots"
    q_chunk: int = 512
    kv_chunk: int = 1024
    rwkv_chunk: int = 16
    ssm_chunk: int = 32
    loss_chunk: int = 1024            # vocab-projection sequence chunking
    # --- paper technique (SC frontend analogue; DESIGN §Arch-applicability)
    first_layer_mode: str = "none"    # "none" | "sc"
    sc_bits: int = 4
    # --- serving (beyond-paper): int8 KV cache with per-token-head scales
    kv_quant: bool = False

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def moe(self) -> MoEConfig | None:
        if self.n_experts == 0:
            return None
        return MoEConfig(self.n_experts, self.top_k, self.d_expert,
                         self.n_shared, self.capacity_factor,
                         self.moe_group_size, impl=self.moe_impl,
                         score=self.router_score,
                         routed_scaling=self.routed_scaling)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Width of one cached latent row (MLA)."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def vocab_padded(self) -> int:
        return -(-self.vocab // 128) * 128

    @property
    def inner(self) -> int:
        return self.d_inner or 2 * self.d_model

    def is_global_layer(self, idx):
        """Vector-friendly: full-attention layer predicate (hybrid family)."""
        if self.window == 0:
            return jnp.ones_like(idx, bool)
        if self.global_every == 0:
            return jnp.zeros_like(idx, bool)
        return (idx % self.global_every) == 0


_GLOBAL_WINDOW = 1 << 30   # "window" so large it never masks


def hybrid_grouped(cfg: "LMConfig") -> bool:
    """Whether the hybrid stack can use the grouped static-window layout."""
    return bool(cfg.window and cfg.global_every
                and cfg.n_layers % cfg.global_every == 0)


def layer_window(cfg: "LMConfig", idx):
    """Per-layer effective window: static 0 if the arch has no windowing,
    else a traced scalar (huge value on global-attention layers)."""
    if cfg.window == 0:
        return 0
    return jnp.where(cfg.is_global_layer(idx), _GLOBAL_WINDOW, cfg.window)


# ==========================================================================
# Param construction (+ mirrored spec tree; abstract mode for the dry-run).
# ==========================================================================

class _Builder:
    def __init__(self, key, cfg: LMConfig, mesh_shape: dict[str, int],
                 abstract: bool):
        self.key = key
        self.cfg = cfg
        self.mesh = mesh_shape or {}
        self.abstract = abstract

    def _split(self):
        if self.abstract:
            return None
        self.key, sub = jax.random.split(self.key)
        return sub

    def dense(self, shape, scale=None):
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, self.cfg.dtype)
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (scale * jax.random.truncated_normal(
            self._split(), -2, 2, shape, jnp.float32)).astype(self.cfg.dtype)

    def fill(self, shape, value, dtype=None):
        dtype = dtype or self.cfg.dtype
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, dtype)
        return jnp.full(shape, value, dtype)

    def fn(self, shape, f):
        if self.abstract:
            return jax.ShapeDtypeStruct(shape, self.cfg.dtype)
        return f().astype(self.cfg.dtype)

    def ok(self, size: int, axis: str) -> bool:
        return size % self.mesh.get(axis, 1) == 0

    def spec(self, shape, logical):
        out = []
        for dim, kind in zip(shape, logical):
            if kind == "tp" and self.ok(dim, MODEL):
                out.append(MODEL)
            elif kind == "fsdp" and self.ok(dim, DATA):
                out.append(DATA)
            else:
                out.append(None)
        return P(*out)


def _attn_params(b: _Builder, L, d, hq, hkv, dh, bias):
    lead = () if L is None else (L,)
    llog = () if L is None else (None,)
    p, s = {}, {}
    for nm, shape, logical in (
            ("wq", (d, hq * dh), ("fsdp", "tp")),
            ("wk", (d, hkv * dh), ("fsdp", "tp")),
            ("wv", (d, hkv * dh), ("fsdp", "tp")),
            ("wo", (hq * dh, d), ("tp", "fsdp"))):
        p[nm] = b.dense(lead + shape)
        s[nm] = b.spec(lead + shape, llog + logical)
    if bias:
        for nm, width, lg in (("bq", hq * dh, "tp"), ("bv", hkv * dh, "tp"),
                              ("bo", d, None)):
            p[nm] = b.fill(lead + (width,), 0.0)
            s[nm] = b.spec(lead + (width,), llog + (lg,))
    return p, s


def _mla_params(b: _Builder, L, d):
    """Latent attention: ``wq`` (d -> H x (nope + rope)), ``w_kv_a``
    (d -> latent + rope key), the latent's RMSNorm, ``w_kv_b`` (latent ->
    H x (nope key + value)) and ``wo`` (H x value -> d)."""
    cfg = b.cfg
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    lead, llog = (L,), (None,)
    p, s = {}, {}
    for nm, shape, logical in (
            ("wq", (d, H * dq), ("fsdp", "tp")),
            ("w_kv_a", (d, cfg.latent_width), ("fsdp", None)),
            ("w_kv_b", (r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
             (None, "tp")),
            ("wo", (H * cfg.v_head_dim, d), ("tp", "fsdp"))):
        p[nm] = b.dense(lead + shape)
        s[nm] = b.spec(lead + shape, llog + logical)
    p["kv_norm"], s["kv_norm"] = _norm_params(b, L, r)
    return p, s


def _mlp_params(b: _Builder, L, d, f, kind, bias):
    lead = () if L is None else (L,)
    llog = () if L is None else (None,)
    p, s = {}, {}
    names = (("w_gate", (d, f), ("fsdp", "tp")),
             ("w_in", (d, f), ("fsdp", "tp")),
             ("w_out", (f, d), ("tp", "fsdp"))) if kind == "swiglu" else \
            (("w_in", (d, f), ("fsdp", "tp")), ("w_out", (f, d), ("tp", "fsdp")))
    for nm, shape, logical in names:
        p[nm] = b.dense(lead + shape)
        s[nm] = b.spec(lead + shape, llog + logical)
    if bias and kind != "swiglu":
        p["b_in"] = b.fill(lead + (f,), 0.0)
        s["b_in"] = b.spec(lead + (f,), llog + ("tp",))
        p["b_out"] = b.fill(lead + (d,), 0.0)
        s["b_out"] = b.spec(lead + (d,), llog + (None,))
    return p, s


def _moe_params(b: _Builder, L, d):
    cfg = b.cfg
    m = cfg.moe
    f = m.d_expert
    p, s = {}, {}
    p["w_router"] = b.dense((L, d, m.n_experts))
    s["w_router"] = P(None, None, None)
    if m.score == "sigmoid":
        p["router_bias"] = b.fill((L, m.n_experts), 0.0, jnp.float32)
        s["router_bias"] = P(None, None)
    for nm, shape, logical in (
            ("w_gate", (L, m.n_experts, d, f), (None, "tp", "fsdp", None)),
            ("w_in", (L, m.n_experts, d, f), (None, "tp", "fsdp", None)),
            ("w_out", (L, m.n_experts, f, d), (None, "tp", None, "fsdp"))):
        p[nm] = b.dense(shape)
        s[nm] = b.spec(shape, logical)
    if m.n_shared:
        sf = m.n_shared * f
        for nm, shape, logical in (
                ("shared_gate", (L, d, sf), (None, "fsdp", "tp")),
                ("shared_in", (L, d, sf), (None, "fsdp", "tp")),
                ("shared_out", (L, sf, d), (None, "tp", "fsdp"))):
            p[nm] = b.dense(shape)
            s[nm] = b.spec(shape, logical)
    return p, s


def _norm_params(b: _Builder, L, d, bias=False):
    lead = () if L is None else (L,)
    p = {"scale": b.fill(lead + (d,), 1.0)}
    s = {"scale": P(*([None] * (len(lead) + 1)))}
    if bias:
        p["bias"] = b.fill(lead + (d,), 0.0)
        s["bias"] = P(*([None] * (len(lead) + 1)))
    return p, s


def _decoder_block_params(b: _Builder, L, *, moe_layer):
    cfg = b.cfg
    p, s = {}, {}
    p["ln1"], s["ln1"] = _norm_params(b, L, cfg.d_model, cfg.use_bias)
    if cfg.mla:
        p["attn"], s["attn"] = _mla_params(b, L, cfg.d_model)
    else:
        p["attn"], s["attn"] = _attn_params(b, L, cfg.d_model, cfg.n_heads,
                                            cfg.n_kv_heads, cfg.d_head,
                                            cfg.use_bias)
    p["ln2"], s["ln2"] = _norm_params(b, L, cfg.d_model, cfg.use_bias)
    if moe_layer:
        p["moe"], s["moe"] = _moe_params(b, L, cfg.d_model)
    else:
        ff = cfg.first_dense_ff if (cfg.family == "moe" and L == 1
                                    and cfg.first_dense_ff) else cfg.d_ff
        p["mlp"], s["mlp"] = _mlp_params(b, L, cfg.d_model, ff,
                                         cfg.mlp_type, cfg.use_bias)
    return p, s


def _cross_block_params(b: _Builder, L):
    """Gated cross-attention decoder block (VLM / whisper decoder)."""
    cfg = b.cfg
    p, s = _decoder_block_params(b, L, moe_layer=False)
    p["ln_x"], s["ln_x"] = _norm_params(b, L, cfg.d_model, cfg.use_bias)
    p["xattn"], s["xattn"] = _attn_params(b, L, cfg.d_model, cfg.n_heads,
                                          cfg.n_kv_heads, cfg.d_head,
                                          cfg.use_bias)
    p["gate_attn"] = b.fill((L,), 0.0 if cfg.family == "vlm" else 1.0)
    s["gate_attn"] = P(None)
    return p, s


def _rwkv_block_params(b: _Builder, L):
    cfg = b.cfg
    d = cfg.d_model
    hd = cfg.n_heads * cfg.d_head
    lora = 64
    p, s = {}, {}
    p["ln1"], s["ln1"] = _norm_params(b, L, d)
    p["ln2"], s["ln2"] = _norm_params(b, L, d)
    p["mu"] = b.fill((L, 7, d), 0.5)    # shift mixes: r,k,v,g,w + cm r,k
    s["mu"] = P(None, None, None)
    for nm in ("wr", "wk", "wv", "wg"):
        p[nm] = b.dense((L, d, hd))
        s[nm] = b.spec((L, d, hd), (None, "fsdp", "tp"))
    p["wo"] = b.dense((L, hd, d))
    s["wo"] = b.spec((L, hd, d), (None, "tp", "fsdp"))
    p["w0"] = b.fill((L, hd), -6.0)     # decay base (w = exp(-exp(.)))
    s["w0"] = P(None, None)
    p["w_lora_a"] = b.dense((L, d, lora))
    s["w_lora_a"] = P(None, None, None)
    p["w_lora_b"] = b.dense((L, lora, hd), scale=0.01)
    s["w_lora_b"] = b.spec((L, lora, hd), (None, None, "tp"))
    p["u"] = b.dense((L, cfg.n_heads, cfg.d_head), scale=0.3)
    s["u"] = P(None, None, None)
    p["ln_wkv"], s["ln_wkv"] = _norm_params(b, L, hd)
    p["cm_k"] = b.dense((L, d, cfg.d_ff))
    s["cm_k"] = b.spec((L, d, cfg.d_ff), (None, "fsdp", "tp"))
    p["cm_v"] = b.dense((L, cfg.d_ff, d))
    s["cm_v"] = b.spec((L, cfg.d_ff, d), (None, "tp", "fsdp"))
    p["cm_r"] = b.dense((L, d, d))
    s["cm_r"] = b.spec((L, d, d), (None, "fsdp", None))
    return p, s


def _hymba_block_params(b: _Builder, L):
    cfg = b.cfg
    d, di, N = cfg.d_model, cfg.inner, cfg.ssm_state
    dtr = cfg.dt_rank or max(16, d // 16)
    p, s = {}, {}
    p["ln1"], s["ln1"] = _norm_params(b, L, d)
    p["attn"], s["attn"] = _attn_params(b, L, d, cfg.n_heads, cfg.n_kv_heads,
                                        cfg.d_head, False)
    p["in_proj"] = b.dense((L, d, 2 * di))
    s["in_proj"] = b.spec((L, d, 2 * di), (None, "fsdp", "tp"))
    p["conv_w"] = b.dense((L, cfg.conv_k, di), scale=0.5)
    s["conv_w"] = b.spec((L, cfg.conv_k, di), (None, None, "tp"))
    p["x_proj"] = b.dense((L, di, dtr + 2 * N))
    s["x_proj"] = b.spec((L, di, dtr + 2 * N), (None, "tp", None))
    p["dt_proj"] = b.dense((L, dtr, di))
    s["dt_proj"] = b.spec((L, dtr, di), (None, None, "tp"))
    p["dt_bias"] = b.fill((L, di), -4.6)
    s["dt_bias"] = b.spec((L, di), (None, "tp"))
    p["A_log"] = b.fn((L, di, N), lambda: jnp.broadcast_to(
        jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (L, di, N)))
    s["A_log"] = b.spec((L, di, N), (None, "tp", None))
    p["D_skip"] = b.fill((L, di), 1.0)
    s["D_skip"] = b.spec((L, di), (None, "tp"))
    p["ssm_out"] = b.dense((L, di, d))
    s["ssm_out"] = b.spec((L, di, d), (None, "tp", "fsdp"))
    p["norm_attn"], s["norm_attn"] = _norm_params(b, L, d)
    p["norm_ssm"], s["norm_ssm"] = _norm_params(b, L, d)
    p["beta"] = b.fill((L, 2), 1.0)
    s["beta"] = P(None, None)
    p["ln2"], s["ln2"] = _norm_params(b, L, d)
    p["mlp"], s["mlp"] = _mlp_params(b, L, d, cfg.d_ff, "swiglu", False)
    return p, s


def init(key, cfg: LMConfig, mesh_shape: dict[str, int] | None = None,
         abstract: bool = False) -> tuple[dict, dict]:
    """Returns (params, specs) — mirrored pytrees.  ``abstract=True`` builds
    ShapeDtypeStructs (no device memory; dry-run input)."""
    b = _Builder(key, cfg, mesh_shape or {}, abstract)
    d, V = cfg.d_model, cfg.vocab_padded
    p: dict = {}
    s: dict = {}
    # embed: vocab on TP only — FSDP on the gathered axis makes SPMD fall
    # back to a full rematerialization of the table (observed; see DESIGN.md)
    p["embed"] = b.dense((V, d), scale=0.02)
    s["embed"] = b.spec((V, d), ("tp", None))
    if not cfg.tie_embeddings:
        p["lm_head"] = b.dense((d, V))
        s["lm_head"] = b.spec((d, V), ("fsdp", "tp"))
    p["final_norm"], s["final_norm"] = _norm_params(b, None, d, cfg.use_bias)
    if cfg.first_layer_mode == "sc":
        # the paper's near-sensor SC first layer as an LM frontend projection
        p["sc_frontend"] = {"w": b.dense((d, d)),
                            "gamma": b.fill((d,), 1.0)}
        s["sc_frontend"] = {"w": b.spec((d, d), (None, None)),
                            "gamma": P(None)}

    fam = cfg.family
    if fam == "moe":
        p["dense0"], s["dense0"] = _decoder_block_params(b, 1, moe_layer=False)
        p["blocks"], s["blocks"] = _decoder_block_params(
            b, cfg.n_layers - 1, moe_layer=True)
    elif fam == "decoder":
        p["blocks"], s["blocks"] = _decoder_block_params(
            b, cfg.n_layers, moe_layer=False)
    elif fam == "rwkv":
        p["blocks"], s["blocks"] = _rwkv_block_params(b, cfg.n_layers)
    elif fam == "hybrid":
        p["blocks"], s["blocks"] = _hymba_block_params(b, cfg.n_layers)
    elif fam == "vlm":
        k = cfg.cross_every
        assert cfg.n_layers % k == 0
        n_groups = cfg.n_layers // k
        p["blocks"], s["blocks"] = _decoder_block_params(
            b, cfg.n_layers - n_groups, moe_layer=False)
        p["cross_blocks"], s["cross_blocks"] = _cross_block_params(b, n_groups)
    elif fam == "encdec":
        p["enc_blocks"], s["enc_blocks"] = _decoder_block_params(
            b, cfg.enc_layers, moe_layer=False)
        p["enc_norm"], s["enc_norm"] = _norm_params(b, None, d, cfg.use_bias)
        p["dec_blocks"], s["dec_blocks"] = _cross_block_params(b, cfg.n_layers)
    else:
        raise ValueError(fam)
    return p, s


def count_params(cfg: LMConfig) -> int:
    params, _ = init(None, cfg, abstract=True)
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


def active_params(cfg: LMConfig) -> int:
    """Per-token active parameters (MoE: shared + top_k of routed)."""
    total = count_params(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    routed = (cfg.n_layers - 1) * m.n_experts * 3 * cfg.d_model * m.d_expert
    active_routed = routed * m.top_k // m.n_experts
    return total - routed + active_routed


# ==========================================================================
# Blocks (forward).
# ==========================================================================

def _norm_apply(cfg, p, x):
    if cfg.norm_type == "layernorm" or "bias" in p:
        return norms.layernorm(x, p["scale"], p.get("bias", 0.0), cfg.norm_eps)
    return norms.rmsnorm(x, p["scale"], cfg.norm_eps)


def _proj(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    return y if b is None else y + b


def _attn_apply(cfg: LMConfig, p, x, positions, *, causal=True, window=0,
                kv_override=None, q_offset=0, kv_prefix=None):
    """Full-sequence attention (train / prefill).  Returns (out, (k, v)).

    ``kv_prefix``: (k, v) of an already-computed (post-RoPE) cache prefix of
    ``q_offset`` positions — suffix-only chunked prefill.  Queries come from
    ``x`` (the suffix, at absolute positions given by ``positions``), keys
    concatenate prefix + suffix, and the returned ``(k, v)`` covers the full
    prefix+suffix length so the caller can assemble the whole cache.  The
    resumed path always attends through ``attend_chunked`` (sliding windows
    become masks): ``attend_sliding``'s tile slicing assumes queries and
    keys start at the same position, which a resumed call violates.

    Bit-exactness is a property of the *chunk schedule*, not of this
    function: chunk j of a block-aligned prefill fold has the same static
    shapes whether the fold started at 0 or resumed at a prefix hit, so XLA
    compiles the identical graph and the outputs match bitwise (see
    ``engine.prefill_chunked``).  A one-shot suffix call is mathematically
    identical to full prefill but may drift in the last ulp — differently
    shaped graphs fuse differently.
    """
    B, S, d = x.shape
    q = _proj(x, p["wq"], p.get("bq")).reshape(B, S, cfg.n_heads, cfg.d_head)
    if kv_override is None:
        k = _proj(x, p["wk"]).reshape(B, -1, cfg.n_kv_heads, cfg.d_head)
        v = _proj(x, p["wv"], p.get("bv")).reshape(B, -1, cfg.n_kv_heads,
                                                   cfg.d_head)
        if cfg.pos_embedding == "rope":
            q = rope.apply_rope(q, positions, cfg.rope_theta)
            k = rope.apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        if cfg.pos_embedding == "rope" and causal:
            q = rope.apply_rope(q, positions, cfg.rope_theta)
    if kv_prefix is not None:
        pk, pv = kv_prefix
        assert pk.shape[1] == q_offset, (pk.shape, q_offset)
        k_full = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
        if (isinstance(window, int) and 0 < window < q_offset and causal
                and kv_override is None):
            # a static-window layer only sees the trailing `window` prefix
            # positions — slice them (static shapes, so the fold's bitwise
            # resume property survives) to keep the O(S·window) bound the
            # one-shot path gets from attend_sliding.  Relative positions
            # are preserved by shifting q_offset with the slice.
            ka = jnp.concatenate(
                [pk[:, q_offset - window:].astype(k.dtype), k], axis=1)
            va = jnp.concatenate(
                [pv[:, q_offset - window:].astype(v.dtype), v], axis=1)
            o = attention.attend_chunked(q, ka, va, causal=True,
                                         window=window, q_offset=window,
                                         q_chunk=cfg.q_chunk,
                                         kv_chunk=cfg.kv_chunk)
        else:
            o = attention.attend_chunked(q, k_full, v_full, causal=causal,
                                         window=window, q_offset=q_offset,
                                         q_chunk=cfg.q_chunk,
                                         kv_chunk=cfg.kv_chunk)
        out = _proj(o.reshape(B, S, cfg.n_heads * cfg.d_head), p["wo"],
                    p.get("bo"))
        return out, (k_full, v_full)
    if (isinstance(window, int) and window > 0 and causal
            and kv_override is None and k.shape[1] == S):
        # static sliding window: true KV skipping (O(S*window) attention)
        o = attention.attend_sliding(q, k, v, window=window,
                                     q_offset=q_offset, q_chunk=cfg.q_chunk)
    else:
        o = attention.attend_chunked(q, k, v, causal=causal, window=window,
                                     q_offset=q_offset, q_chunk=cfg.q_chunk,
                                     kv_chunk=cfg.kv_chunk)
    out = _proj(o.reshape(B, S, cfg.n_heads * cfg.d_head), p["wo"],
                p.get("bo"))
    return out, (k, v)


def mla_project(cfg: LMConfig, p, x, positions):
    """Latent attention's per-token projections of ``x`` (B, S, d) at
    ``positions`` (B, S).  Returns ``(q, rows)``: q (B, S, H, nope + rope)
    with RoPE on its last ``qk_rope_head_dim`` lanes, and the cache rows
    (B, S, kv_lora_rank + qk_rope_head_dim) — the RMS-normed latent beside
    the RoPE'd key part that every head shares."""
    B, S, _ = x.shape
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = _proj(x, p["wq"]).reshape(B, S, cfg.n_heads,
                                  dn + cfg.qk_rope_head_dim)
    q = jnp.concatenate(
        [q[..., :dn], rope.apply_rope(q[..., dn:], positions, cfg.rope_theta)],
        axis=-1)
    kv = _proj(x, p["w_kv_a"])
    c = norms.rmsnorm(kv[..., :r], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope.apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    return q, jnp.concatenate([c, k_pe[..., 0, :]], axis=-1)


def mla_scale(cfg: LMConfig) -> float:
    """Softmax scale of latent attention: the query/key head width."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def _mla_apply(cfg: LMConfig, p, x, positions, *, causal=True, window=0,
               q_offset=0, kv_prefix=None):
    """Latent attention over a whole sequence (train / prefill) in its
    expanded form: the latent rows of prefix + chunk are projected up by
    ``w_kv_b`` to per-head keys (beside the shared RoPE part) and values.
    ``kv_prefix``: the latent rows (B, q_offset, W) of an already-computed
    prefix.  Returns (out, rows) with rows covering prefix + chunk — the
    cache this layer owes."""
    assert isinstance(window, int) and window == 0, \
        "latent attention has no sliding window"
    B, S, _ = x.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    assert dv <= dn + dr, (dv, dn, dr)
    q, rows = mla_project(cfg, p, x, positions)
    if kv_prefix is not None:
        assert kv_prefix.shape[1] == q_offset, (kv_prefix.shape, q_offset)
        rows = jnp.concatenate([kv_prefix.astype(rows.dtype), rows], axis=1)
    T = rows.shape[1]
    kv = _proj(rows[..., :r], p["w_kv_b"]).reshape(B, T, H, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(rows[:, :, None, r:], (B, T, H, dr))],
        axis=-1)
    # attend_chunked takes one head width for q, k and v: the values ride
    # zero-padded to the query width, and the padding is cut off after
    v = jnp.pad(kv[..., dn:], ((0, 0), (0, 0), (0, 0), (0, dn + dr - dv)))
    o = attention.attend_chunked(q, k, v, causal=causal, q_offset=q_offset,
                                 q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
    out = _proj(o[..., :dv].reshape(B, S, H * dv), p["wo"])
    return out, rows


def mla_absorb(cfg: LMConfig, p, q):
    """Fold ``w_kv_b``'s key half into decode queries: q (B, H, nope + rope)
    -> (B, H, kv_lora_rank + rope), whose dot with a cached latent row is
    the expanded form's score (before the softmax scale)."""
    H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
    wkb = p["w_kv_b"].reshape(r, H, dn + cfg.v_head_dim)[..., :dn]
    q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :dn], wkb,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q.dtype), q[..., dn:]], axis=-1)


def mla_unabsorb(cfg: LMConfig, p, o_lat):
    """Decode attention output over the latent, (B, H, kv_lora_rank), through
    ``w_kv_b``'s value half and ``wo``: the layer's output (B, 1, d)."""
    B, H, r = o_lat.shape
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    wvb = p["w_kv_b"].reshape(r, H, dn + dv)[..., dn:]
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wvb)
    return _proj(o.reshape(B, 1, H * dv), p["wo"])


def attn_decode_paged_mla(cfg: LMConfig, p, x1, lat_blocks, tables, pos, *,
                          backend, layer=None, interpret=None):
    """One-token latent attention for a batch of slots in its absorbed
    form, reading the latent rows in place from the paged arena.

    x1: (S, 1, d) normed activations; lat_blocks: one layer's slice
    (num_blocks, 1, bs, W) of the latent arena, or with ``layer``
    (``"pallas"`` only) the whole (L, num_blocks, 1, bs, W) arena read at
    that layer; tables (S, nb); pos (S,) the new token's row index.  The
    new row is overlaid at ``pos`` by the read (it reaches the arena after
    the layer scan).  Returns (out (S, 1, d), row (S, W))."""
    q, row = mla_project(cfg, p, x1, pos[:, None])
    if layer is None:
        lat_blocks = lat_blocks[:, 0]           # (num_blocks, bs, W)
    o = attention.attend_decode_latent(
        mla_absorb(cfg, p, q[:, 0]), lat_blocks, tables, pos + 1,
        new_row=row[:, 0], scale=mla_scale(cfg), dv=cfg.kv_lora_rank,
        backend=backend, layer=layer, interpret=interpret)
    return mla_unabsorb(cfg, p, o), row[:, 0]


def attn_decode_qkv(cfg: LMConfig, p, x1, pos):
    """One decode token's attention projections: x1 (B, 1, d) at positions
    ``pos`` (B,).  Returns q (B, 1, H, Dh) and the post-RoPE k1 beside v1
    (B, 1, Hkv, Dh), the row the token owes its cache."""
    B = x1.shape[0]
    q = _proj(x1, p["wq"], p.get("bq")).reshape(B, 1, cfg.n_heads, cfg.d_head)
    k1 = _proj(x1, p["wk"]).reshape(B, 1, cfg.n_kv_heads, cfg.d_head)
    v1 = _proj(x1, p["wv"], p.get("bv")).reshape(B, 1, cfg.n_kv_heads,
                                                 cfg.d_head)
    if cfg.pos_embedding == "rope":
        posb = pos[:, None]
        q = rope.apply_rope(q, posb, cfg.rope_theta)
        k1 = rope.apply_rope(k1, posb, cfg.rope_theta)
    return q, k1, v1


def attn_decode_out(p, o):
    """A decode attention's heads o (B, 1, H, Dh) through ``wo``: (B, 1, d)."""
    return _proj(o.reshape(o.shape[0], 1, -1), p["wo"], p.get("bo"))


def attn_decode_paged(cfg: LMConfig, p, x1, k_blocks, v_blocks, tables, pos,
                      *, backend, window=0, interpret=None, scales=None,
                      cascade=None, layer=None):
    """One-token decode attention for a batch of slots, reading K/V in
    place from one layer's slice of the paged block arena.

    x1: (S, 1, d) normed activations (S = slot lanes); k_blocks, v_blocks:
    (num_blocks, 1, bs, Hkv, Dh) — one layer of ``engine.init_paged_arena``;
    tables: (S, nb) int32 arena block ids; pos: (S,) int32 per-lane lengths
    (the new token's row index).  ``window`` may be traced (per-layer
    sliding/global selection).  Returns (out (S, 1, d), k1, v1) with k1/v1
    the (S, Hkv, Dh) post-RoPE rows the caller scatters into the arena —
    the tick's only persistent sequence-axis write.

    The new token's row has not reached the arena yet when attention runs,
    so every backend overlays it at position ``pos`` functionally: the XLA
    reference (:func:`nn.attention.attend_decode_paged`) splices it into
    the gathered view — bitwise-identical to the dense
    ``engine.decode_step`` attention, which the paged parity suite pins —
    and ``"pallas"`` hands it to ``kernels.paged_attn`` as a row operand
    overlaid in VMEM (an arena-slice update here would copy every block of
    the layer, live or not — the very traffic the kernel's per-block DMA
    exists to avoid).

    ``scales``: optional (k_scale_blocks, v_scale_blocks) — one layer's
    slice of the int8 ``kv_quant`` scale arenas.  The new row is quantized
    post-RoPE (exactly the dense cache's write) and attention reads the
    dequantized gathered view with the *dequantized-quantized* row spliced
    in — what the dense quant tick sees after its write — so in-place
    quant decode stays bitwise against the gather-tick oracle.  Returns
    (out, k1q, v1q, k1_scale, v1_scale) in that case; only ``"xla"`` covers
    the quant layout (assert).

    ``backend`` ("xla" | "pallas" | "cascade", plus ``cascade=`` group
    metadata for the last — see :mod:`repro.serve.backend`) is the read-
    path dispatch forwarded to :func:`nn.attention.attend_decode_paged`.

    ``layer`` (``"pallas"`` only): ``k_blocks``/``v_blocks`` are the whole
    layer-leading ``(L, num_blocks, 1, bs, Hkv, Dh)`` arena, handed to the
    kernel as it lies in HBM, and the kernel reads this layer of it.
    """
    q, k1, v1 = attn_decode_qkv(cfg, p, x1, pos)
    if layer is None:
        kb, vb = k_blocks[:, 0], v_blocks[:, 0]  # (num_blocks, bs, Hkv, Dh)
    else:
        kb, vb = k_blocks, v_blocks
    new_kv, scale_rows = (k1[:, 0], v1[:, 0]), ()
    if scales is not None:
        assert backend == "xla", \
            "only the XLA reference covers the int8 kv_quant layout"
        from repro.serve import kvquant
        (k1, k1s), (v1, v1s) = kvquant.quantize(k1), kvquant.quantize(v1)
        new_kv = (kvquant.dequantize(k1, k1s, cfg.dtype)[:, 0],
                  kvquant.dequantize(v1, v1s, cfg.dtype)[:, 0])
        scales = (scales[0][:, 0], scales[1][:, 0])
        scale_rows = (k1s[:, 0], v1s[:, 0])
    o = attention.attend_decode_paged(q, kb, vb, tables, pos + 1,
                                      window=window, new_kv=new_kv,
                                      scales=scales, out_dtype=cfg.dtype,
                                      backend=backend, cascade=cascade,
                                      layer=layer, interpret=interpret)
    return (attn_decode_out(p, o), k1[:, 0], v1[:, 0]) + scale_rows


def _mlp_apply(cfg: LMConfig, p, x, kind=None):
    kind = kind or cfg.mlp_type
    if "w_gate" in p:
        return mlp_lib.swiglu(x, p["w_gate"], p["w_in"], p["w_out"])
    return mlp_lib.gelu_mlp(x, p["w_in"], p.get("b_in", 0.0), p["w_out"],
                            p.get("b_out", 0.0))


def decoder_block(cfg: LMConfig, p, x, positions, *, window=0, moe_layer=False,
                  q_offset=0, causal=True, kv_prefix=None, moe_dropless=False):
    """Pre-norm transformer block.  Returns (x, kv, aux); ``kv`` is the
    (k, v) pair, or the latent rows under latent attention (``cfg.mla``).

    ``kv_prefix`` + ``q_offset`` resume from an existing KV prefix
    (suffix-only chunked prefill); ``kv`` then spans prefix + suffix.
    ``moe_dropless`` routes the MoE FFN with one whole-sequence dispatch
    group and never drops a token (serving prefill: a token's output must
    not depend on the rest of its dispatch group, or a prompt could not be
    resumed from a cached prefix — see ``cfg.moe_dropless_prefill``).
    """
    x = hint(x, "batch", None, None)
    attend = _mla_apply if cfg.mla else _attn_apply
    h, kv = attend(cfg, p["attn"], _norm_apply(cfg, p["ln1"], x),
                   positions, causal=causal, window=window,
                   q_offset=q_offset, kv_prefix=kv_prefix)
    x = x + h
    z = _norm_apply(cfg, p["ln2"], x)
    if moe_layer:
        mcfg = cfg.moe
        if moe_dropless:
            mcfg = dataclasses.replace(
                mcfg, group_size=z.shape[0] * z.shape[1], dropless=True)
        y, aux = moe_ffn(z, p["moe"], mcfg)
    else:
        y, aux = _mlp_apply(cfg, p["mlp"], z), jnp.float32(0.0)
    return x + y, kv, aux


def cross_block(cfg: LMConfig, p, x, positions, enc_kv, *, q_offset=0,
                kv_prefix=None):
    """Self-attn + gated cross-attn + mlp (VLM cross layer, whisper decoder).

    ``kv_prefix`` resumes the causal self-attention from an existing KV
    prefix; the cross-attention needs no prefix (its K/V are the fixed
    encoder projections and each query row is independent of the others).
    """
    h, kv = _attn_apply(cfg, p["attn"], _norm_apply(cfg, p["ln1"], x),
                        positions, causal=True, q_offset=q_offset,
                        kv_prefix=kv_prefix)
    x = x + h
    hx, _ = _attn_apply(cfg, p["xattn"], _norm_apply(cfg, p["ln_x"], x),
                        positions, causal=False, kv_override=enc_kv)
    gate = jnp.tanh(p["gate_attn"].astype(jnp.float32)).astype(x.dtype)
    x = x + gate * hx
    y = _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
    return x + y, kv


def _token_shift(x, last):
    """(B, S, d) shifted right by one; ``last`` (B, d) fills position 0."""
    return jnp.concatenate([last[:, None, :], x[:, :-1]], axis=1)


def rwkv_block(cfg: LMConfig, p, x, state):
    """RWKV6 block.  state: {"wkv": (B,H,D,D) f32, "shift1": (B,d),
    "shift2": (B,d)}.  Returns (x, new_state)."""
    B, S, d = x.shape
    H, Dh = cfg.n_heads, cfg.d_head
    xa = _norm_apply(cfg, p["ln1"], x)
    xs = _token_shift(xa, state["shift1"])
    mu = p["mu"]
    mix = lambda i: xa + (xs - xa) * mu[i]
    r = _proj(mix(0), p["wr"]).reshape(B, S, H, Dh)
    k = _proj(mix(1), p["wk"]).reshape(B, S, H, Dh)
    v = _proj(mix(2), p["wv"]).reshape(B, S, H, Dh)
    g = _proj(mix(3), p["wg"])
    ww = p["w0"].astype(jnp.float32) + jnp.einsum(
        "bsl,lh->bsh", jnp.einsum("bsd,dl->bsl", mix(4), p["w_lora_a"]),
        p["w_lora_b"]).astype(jnp.float32)
    w = jnp.exp(-jnp.exp(ww)).reshape(B, S, H, Dh)
    if S == 1:   # decode: O(1) recurrent step
        o1, wkv_state = ssm.wkv6_step(r[:, 0], k[:, 0], v[:, 0],
                                      w[:, 0].astype(x.dtype), p["u"],
                                      state["wkv"])
        wkv = o1[:, None].astype(x.dtype)
    else:
        wkv, wkv_state = ssm.wkv6_chunked(r, k, v, w.astype(x.dtype), p["u"],
                                          chunk=min(cfg.rwkv_chunk, S),
                                          state0=state["wkv"])
    wkv = norms.rmsnorm(wkv.reshape(B, S, H * Dh), p["ln_wkv"]["scale"],
                        cfg.norm_eps)
    att = _proj(wkv * jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype),
                p["wo"])
    x = x + att
    xc = _norm_apply(cfg, p["ln2"], x)
    xcs = _token_shift(xc, state["shift2"])
    kr = xc + (xcs - xc) * mu[5]
    rr = xc + (xcs - xc) * mu[6]
    kk = jnp.square(jax.nn.relu(_proj(kr, p["cm_k"]).astype(jnp.float32))
                    ).astype(x.dtype)
    cm = jax.nn.sigmoid(_proj(rr, p["cm_r"]).astype(jnp.float32)
                        ).astype(x.dtype) * _proj(kk, p["cm_v"])
    x = x + cm
    new_state = {"wkv": wkv_state, "shift1": xa[:, -1], "shift2": xc[:, -1]}
    return x, new_state


def _causal_conv(x, w, prev):
    """Depthwise causal conv: x (B,S,di), w (K,di), prev (B,K-1,di)."""
    K = w.shape[0]
    xp = jnp.concatenate([prev, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return out, xp[:, -(K - 1):]


def hymba_block(cfg: LMConfig, p, x, positions, state, *, window, q_offset=0,
                kv_prefix=None):
    """Parallel GQA + Mamba block.  state: {"conv": (B,K-1,di),
    "ssm": (B,di,N) f32}.  Returns (x, kv, new_state).

    ``state`` is the recurrent boundary condition: fresh zeros for a
    from-scratch prefill, or the conv taps / SSM state at position
    ``q_offset`` when resuming with ``kv_prefix`` (chunked prefill)."""
    B, S, d = x.shape
    z = _norm_apply(cfg, p["ln1"], x)
    att, kv = _attn_apply(cfg, p["attn"], z, positions, causal=True,
                          window=window, q_offset=q_offset,
                          kv_prefix=kv_prefix)
    xz = _proj(z, p["in_proj"])
    xm, gate = jnp.split(xz, 2, axis=-1)
    xm, conv_state = _causal_conv(xm, p["conv_w"], state["conv"])
    xm = jax.nn.silu(xm.astype(jnp.float32)).astype(x.dtype)
    dtr = p["dt_proj"].shape[0]
    dbc = _proj(xm, p["x_proj"])
    dt = jax.nn.softplus(
        _proj(dbc[..., :dtr], p["dt_proj"]).astype(jnp.float32)
        + p["dt_bias"].astype(jnp.float32))
    N = cfg.ssm_state
    Bm, Cm = dbc[..., dtr:dtr + N], dbc[..., dtr + N:]
    y, ssm_state = ssm.selective_scan(xm, dt.astype(x.dtype), p["A_log"],
                                      Bm, Cm, p["D_skip"],
                                      chunk=min(cfg.ssm_chunk, S),
                                      state0=state["ssm"])
    y = y * jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
    y = _proj(y, p["ssm_out"])
    beta = p["beta"].astype(jnp.float32)
    mixed = (beta[0] * _norm_apply(cfg, p["norm_attn"], att).astype(jnp.float32)
             + beta[1] * _norm_apply(cfg, p["norm_ssm"], y).astype(jnp.float32)
             ) * 0.5
    x = x + mixed.astype(x.dtype)
    x = x + _mlp_apply(cfg, p["mlp"], _norm_apply(cfg, p["ln2"], x))
    return x, kv, {"conv": conv_state, "ssm": ssm_state}


# ==========================================================================
# Whole-model forward (train) — scan over layers + remat.
# ==========================================================================

def _maybe_remat(cfg, f):
    if cfg.remat == "none":
        return f
    if cfg.remat == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(f)


def _sinusoidal(S, d, offset=0):
    pos = jnp.arange(offset, offset + S, dtype=jnp.float32)[:, None]
    i = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, i / d)
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe


def sc_frontend(cfg: LMConfig, p, x):
    """The paper's technique as an LM frontend (DESIGN §Arch-applicability):
    the first projection runs in the simulated stochastic domain — split
    pos/neg unipolar weights, TFF adder tree, sign activation — with a
    straight-through estimator so the binary remainder retrains around it
    (exactly the paper's recovery mechanism).

    Functional-sim cost is O(d) table gathers per output; intended for the
    near-sensor-scale modality frontends and smoke configs — the dry-run
    roofline cells keep it off (see DESIGN §5).
    """
    from repro.core import sc_layer
    B, S, d = x.shape
    # sensor normalization: map activations into [0, 1] per feature vector
    lo = jnp.min(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True)
    x01 = ((x - lo) / jnp.maximum(hi - lo, 1e-6)).astype(jnp.float32)
    w = p["w"].astype(jnp.float32)
    sc_cfg = sc_layer.SCConfig(bits=cfg.sc_bits)
    sc_out = sc_layer.sc_dot_sign(x01, w, sc_cfg)          # {-1, 0, 1}
    # straight-through: forward = SC sim, backward = the linear surrogate
    lin = jnp.einsum("bsd,df->bsf", x01, w)
    out = jax.lax.stop_gradient(sc_out - lin) + lin
    return (out * p["gamma"].astype(jnp.float32)).astype(x.dtype)


def embed_tokens(cfg: LMConfig, params, tokens, pos_offset: int = 0):
    """``pos_offset``: absolute position of tokens[0] (suffix-only prefill
    embeds its tokens at their true positions, not from 0)."""
    x = params["embed"][tokens]
    if cfg.pos_embedding == "sinusoidal":
        x = x + _sinusoidal(tokens.shape[1], cfg.d_model,
                            offset=pos_offset).astype(x.dtype)[None]
    if cfg.first_layer_mode == "sc":
        x = x + sc_frontend(cfg, params["sc_frontend"], x)   # residual insert
    return hint(x, "batch", None, None)


def _stack_scan(cfg, params_stacked, body, x, xs_extra=None):
    """Scan ``body`` over the leading layer axis of ``params_stacked``.

    body(layer_params, x, extra) -> (x, per_layer_output)
    """
    L = jax.tree.leaves(params_stacked)[0].shape[0]
    wrapped = _maybe_remat(cfg, body)

    def scan_fn(carry, inp):
        lp, extra = inp
        return wrapped(lp, carry, extra)

    xs = (params_stacked,
          xs_extra if xs_extra is not None else jnp.zeros((L,), jnp.int32))
    return jax.lax.scan(scan_fn, x, xs)


def forward(cfg: LMConfig, params, batch) -> tuple[jax.Array, dict]:
    """Training forward: next-token cross-entropy.

    batch: {"tokens": (B,S) i32, "labels": (B,S) i32 (-1 = ignore),
            optional "enc_embed": (B,Tenc,d), "vision_embed": (B,Tv,d)}.
    Returns (loss, metrics).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    aux_total = jnp.float32(0.0)
    fam = cfg.family

    if fam in ("decoder", "moe"):
        if fam == "moe":
            p0 = jax.tree.map(lambda a: a[0], params["dense0"])
            x, _, _ = decoder_block(cfg, p0, x, positions)

        def body(lp, x, idx):
            w = layer_window(cfg, idx)
            x, _, aux = decoder_block(cfg, lp, x, positions,
                                      window=w, moe_layer=(fam == "moe"))
            return x, aux
        L = cfg.n_layers - (1 if fam == "moe" else 0)
        x, auxs = _stack_scan(cfg, params["blocks"], body, x,
                              jnp.arange(L, dtype=jnp.int32))
        aux_total = jnp.sum(auxs)

    elif fam == "rwkv":
        def body(lp, x, _):
            state = {"wkv": jnp.zeros((B, cfg.n_heads, cfg.d_head, cfg.d_head),
                                      jnp.float32),
                     "shift1": jnp.zeros((B, cfg.d_model), x.dtype),
                     "shift2": jnp.zeros((B, cfg.d_model), x.dtype)}
            x, _ = rwkv_block(cfg, lp, x, state)
            return x, jnp.float32(0.0)
        x, _ = _stack_scan(cfg, params["blocks"], body, x)

    elif fam == "hybrid":
        def fresh_state():
            return {"conv": jnp.zeros((B, cfg.conv_k - 1, cfg.inner),
                                      x.dtype),
                    "ssm": jnp.zeros((B, cfg.inner, cfg.ssm_state),
                                     jnp.float32)}

        if hybrid_grouped(cfg):
            # [1 global + (g-1) sliding] x G groups with STATIC windows, so
            # sliding layers get true KV skipping (attend_sliding)
            G, ge = cfg.n_layers // cfg.global_every, cfg.global_every
            grouped = jax.tree.map(
                lambda a: a.reshape((G, ge) + a.shape[1:]), params["blocks"])

            def group_body(gp, x, _):
                g0 = jax.tree.map(lambda a: a[0], gp)
                rest = jax.tree.map(lambda a: a[1:], gp)
                x, _, _ = hymba_block(cfg, g0, x, positions, fresh_state(),
                                      window=0)

                def inner(lp, x, __):
                    x, _, _ = hymba_block(cfg, lp, x, positions,
                                          fresh_state(), window=cfg.window)
                    return x, jnp.float32(0.0)
                x, _ = _stack_scan(cfg, rest, inner, x)
                return x, jnp.float32(0.0)

            def outer(carry, gp):
                return _maybe_remat(cfg, group_body)(gp, carry, None)
            x, _ = jax.lax.scan(outer, x, grouped)
        else:
            def body(lp, x, idx):
                x, _, _ = hymba_block(cfg, lp, x, positions, fresh_state(),
                                      window=layer_window(cfg, idx))
                return x, jnp.float32(0.0)
            x, _ = _stack_scan(cfg, params["blocks"], body, x,
                               jnp.arange(cfg.n_layers, dtype=jnp.int32))

    elif fam == "vlm":
        vis = batch["vision_embed"].astype(x.dtype)
        enc_kv = None  # per-cross-layer KV computed from vis inside the block
        k = cfg.cross_every
        n_groups = cfg.n_layers // k
        self_pp = jax.tree.map(
            lambda a: a.reshape((n_groups, k - 1) + a.shape[1:]),
            params["blocks"])

        def group_body(gp, x, _):
            self_p, cross_p = gp

            def inner(lp, x, __):
                x, _, _ = decoder_block(cfg, lp, x, positions)
                return x, jnp.float32(0.0)
            x, _ = _stack_scan(cfg, self_p, inner, x)
            kx = _proj(vis, cross_p["xattn"]["wk"]).reshape(
                B, -1, cfg.n_kv_heads, cfg.d_head)
            vx = _proj(vis, cross_p["xattn"]["wv"]).reshape(
                B, -1, cfg.n_kv_heads, cfg.d_head)
            x, _ = cross_block(cfg, cross_p, x, positions, (kx, vx))
            return x, jnp.float32(0.0)

        def outer(carry, inp):
            return _maybe_remat(cfg, group_body)(inp, carry, None)
        x, _ = jax.lax.scan(outer, x, (self_pp, params["cross_blocks"]))

    elif fam == "encdec":
        enc = batch["enc_embed"].astype(x.dtype)
        enc = enc + _sinusoidal(enc.shape[1], cfg.d_model
                                ).astype(enc.dtype)[None]
        enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1]),
                                   (B, enc.shape[1]))

        def enc_body(lp, h, _):
            h, _, _ = decoder_block(cfg, lp, h, enc_pos, causal=False)
            return h, jnp.float32(0.0)
        enc, _ = _stack_scan(cfg, params["enc_blocks"], enc_body, enc)
        enc = _norm_apply(cfg, params["enc_norm"], enc)

        def dec_body(lp, x, _):
            kx = _proj(enc, lp["xattn"]["wk"]).reshape(
                B, -1, cfg.n_kv_heads, cfg.d_head)
            vx = _proj(enc, lp["xattn"]["wv"], lp["xattn"].get("bv")).reshape(
                B, -1, cfg.n_kv_heads, cfg.d_head)
            x, _ = cross_block(cfg, lp, x, positions, (kx, vx))
            return x, jnp.float32(0.0)
        x, _ = _stack_scan(cfg, params["dec_blocks"], dec_body, x)
    else:
        raise ValueError(fam)

    x = _norm_apply(cfg, params["final_norm"], x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    loss, n_tok = chunked_xent(cfg, x, head, batch["labels"])
    total = loss + 0.01 * aux_total
    return total, {"loss": loss, "aux": aux_total, "tokens": n_tok}


def moe_ffn_decode(cfg: LMConfig, moe_params, z):
    """MoE FFN for a (B, 1, d) decode activation.  Every lane's token is a
    dispatch group of its own, so a lane's output never depends on the
    other lanes of its batch (the paged tick's parity contract)."""
    m = dataclasses.replace(cfg.moe, group_size=1)
    return jax.vmap(lambda zi: moe_ffn(zi[None], moe_params, m)[0][0])(z)


def moe_ffn_decode_rows(cfg: LMConfig, tokens: int) -> int:
    """Rows the expert FFN of :func:`moe_ffn_decode` computes for a
    ``(tokens, 1, d)`` activation: ``tokens`` dispatch groups of one."""
    return tokens * expert_rows(dataclasses.replace(cfg.moe, group_size=1),
                                1)


def chunked_xent(cfg: LMConfig, x, head, labels):
    """Cross-entropy with the vocab projection chunked over sequence (the
    (S, V) logits for a 128k vocab never materialize at full length)."""
    B, S, d = x.shape
    ck = min(cfg.loss_chunk, S)
    nc = -(-S // ck)
    xp = jnp.pad(x, ((0, 0), (0, nc * ck - S), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, nc * ck - S)), constant_values=-1)
    xc = xp.reshape(B, nc, ck, d).swapaxes(0, 1)
    lc = lp.reshape(B, nc, ck).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(xi, li):
        logits = hint(jnp.einsum("bsd,dv->bsv", xi, head,
                                 preferred_element_type=jnp.float32),
                      "batch", None, "model")
        mask = li >= 0
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(li, 0)[..., None],
                                 axis=-1)[..., 0]
        return jnp.sum((lse - ll) * mask), jnp.sum(mask)

    def body(carry, inp):
        tot, cnt = carry
        l, n = chunk_loss(*inp)
        return (tot + l, cnt + n), None

    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                 (xc, lc))
    return tot / jnp.maximum(cnt, 1.0), cnt
