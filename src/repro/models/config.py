"""Unified, hashable model configuration for every supported family."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core import factory

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}

FAMILIES = ("lm", "moe", "encdec", "ssm", "vlm", "hybrid")


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    name: str
    family: str
    n_layers: int
    d_model: int
    vocab_size: int
    # attention
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    rope_theta: Optional[float] = 10000.0
    qk_norm: bool = False
    qkv_bias: bool = False
    # latent attention (MLA, on where kv_lora_rank > 0): q of
    # qk_nope + qk_rope per head straight from x (q_lora_rank None: no q
    # compression), a kv_lora_rank latent plus one shared rope key, values
    # of v_head_dim per head
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    window: Optional[int] = None          # sliding-window attention
    attn_chunk: Optional[int] = None      # online-softmax key chunking
    # route sdpa through the Pallas flash kernels (prefill grid + ring-cache
    # decode) when the kernel route is active; off-TPU the chunked/naive
    # einsum paths remain the hot path (REPRO_KERNEL_ATTN forces either)
    flash_attn: bool = False
    # ff
    d_ff: int = 0
    act: str = "swiglu"
    mlp_bias: bool = False
    # norm / embeddings
    norm: str = "rmsnorm"                 # "rmsnorm" | "layernorm"
    norm_eps: Optional[float] = None      # None: rmsnorm 1e-6, layernorm 1e-5
    pos_embed: str = "rope"               # "rope" | "learned" | "none"
    max_position: int = 1 << 20
    tie_embeddings: bool = False
    # one-hot (iota) embedding lookup: keeps the vocab-sharded table's
    # gradient a plain matmul (no giant scatter under GSPMD) — the
    # Megatron/MaxText trick.  On for production configs.
    iota_embed: bool = False
    # moe
    n_experts: int = 0
    n_experts_padded: int = 0
    top_k: int = 0
    n_shared: int = 0
    expert_d_ff: int = 0
    capacity_factor: float = 1.25
    # tokens at a time through an expert layer: the GShard layer's sequence
    # chunks, the share layer's chunks of its sorted-row buffer
    moe_chunk: Optional[int] = None
    router_aux_coef: float = 0.01
    # a moe stack's leading dense layers (their ff is d_ff wide)
    n_dense_layers: int = 0
    # router: scores "softmax" | "sigmoid" (with a per-expert selection
    # bias that picks the top_k and does not weigh them), the chosen
    # scores normalised to sum 1 (norm_topk) and scaled by routed_scale
    router_score: str = "softmax"
    norm_topk: bool = True
    routed_scale: float = 1.0
    # the experts this chip holds, ids expert_first .. + n_experts_held:
    # the dropless expert layer of one chip's share (routing over all
    # n_experts).  0 holds all e_pad experts in the GShard capacity layer.
    expert_first: int = 0
    n_experts_held: int = 0
    # ssm (mamba2 / hybrid)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    conv_width: int = 4
    ssd_chunk: int = 256
    # encoder-decoder
    n_enc_layers: int = 0
    n_frames: int = 0
    frontend_dim: int = 0
    # vlm
    n_patches: int = 0
    # the paper's knob
    linear: factory.LinearCfg = factory.DENSE
    # serving-only KV-cache quantization: "int8" stores paged K/V pools as
    # int8 payloads with per-token-row fp32 scale pools; the paged decode
    # kernel dequantizes tiles in-kernel after the block-table gather.
    # None keeps the cache dtype the engine asks for.  Engines plumb this
    # to init_paged_kv_cache; REPRO_KERNEL_QUANT=off disables it.
    kv_quant: Optional[str] = None
    # precision & memory
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = False
    # training-shape hints consumed by the launcher
    grad_accum: int = 1

    def __post_init__(self):
        if self.family not in FAMILIES:
            raise ValueError(f"unknown family {self.family!r}")
        if self.q_lora_rank:
            raise ValueError("latent attention with q compression "
                             "(q_lora_rank) is not implemented")

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def pdtype(self):
        return _DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return _DTYPES[self.compute_dtype]

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def e_pad(self) -> int:
        return self.n_experts_padded or self.n_experts

    def replace(self, **kw) -> "ModelCfg":
        return dataclasses.replace(self, **kw)
