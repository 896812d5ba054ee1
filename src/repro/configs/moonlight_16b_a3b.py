"""moonlight-16b-a3b [moe] — the DeepSeek-V3 block at d2048: 27L, 16H
latent attention (kv_lora_rank 512, qk 128 nope + 64 rope, v 128, no q
compression, rope theta 5e4); layer 0 dense SwiGLU d_ff=11264, layers
1-26 64 routed experts of 1408 (sigmoid scores, selection bias, top-6,
normalised, x2.446) + 2 shared (one SwiGLU of 2816); RMSNorm eps 1e-5;
untied head over v=163840.
[hf:moonshotai/Moonlight-16B-A3B config.json]

``full()`` holds every expert (``n_experts_held=64``); a deployment that
shares each layer over chips sets the share (``expert_first``,
``n_experts_held``)."""
from repro.configs.base import DYAD_DEFAULT
from repro.models.config import ModelCfg


def full() -> ModelCfg:
    return ModelCfg(
        name="moonlight-16b-a3b", family="moe",
        n_layers=27, d_model=2048, vocab_size=163840,
        n_heads=16, n_kv_heads=16, head_dim=192, rope_theta=50000.0,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        n_dense_layers=1, d_ff=11264, act="swiglu",
        n_experts=64, top_k=6, expert_d_ff=1408, n_shared=2,
        router_score="sigmoid", norm_topk=True, routed_scale=2.446,
        router_aux_coef=0.0, n_experts_held=64, moe_chunk=4096,
        norm_eps=1e-5, max_position=8192,
        flash_attn=True, iota_embed=True,
        linear=DYAD_DEFAULT,
        compute_dtype="bfloat16",
    )


def smoke() -> ModelCfg:
    return full().replace(
        name="moonlight-16b-a3b-smoke", n_layers=3, d_model=64,
        vocab_size=256, n_heads=4, n_kv_heads=4, head_dim=24,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, d_ff=128, n_experts=16, top_k=4, expert_d_ff=32,
        n_experts_held=4, expert_first=4, moe_chunk=None,
        compute_dtype="float32")
