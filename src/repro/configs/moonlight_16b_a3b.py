"""Moonlight-16B-A3B [moe]: 27L d_model=2048; MLA, 16 heads, kv_lora 512,
no q low-rank (q projected straight from x, no q norm), qk 128 nope + 64
rope, v 128; layer 0 a dense SwiGLU of 11,264; 26 MoE layers of 64 routed
experts of 1,408, top-6, plus 2 shared experts; sigmoid noaux_tc routing (a
correction bias picks, the unbiased scores normalised over the 6 and
x 2.446 weigh); vocab 163,840, rope theta 50,000, untied
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3].
The engine serves it dropless, as it serves every MoE model."""
import dataclasses

from .base import MLAConfig, MoEConfig, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe", num_layers=27, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=1408, vocab_size=163840,
    max_seq_len=8192, rope_theta=50000.0, norm_eps=1e-5, attn_kind="mla",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, scoring="sigmoid",
                  norm_topk_prob=True, routed_scaling_factor=2.446),
    first_dense_layers=1, dense_d_ff=11264)
STRATEGY = "tp"

# 1 dense + 2 MoE layers; 8 routed experts of which this chip holds 4
REDUCED = CONFIG.replace(
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=32,
    vocab_size=128, dense_d_ff=96,
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
                  qk_rope_head_dim=8, v_head_dim=16),
    moe=dataclasses.replace(CONFIG.moe, num_experts=8, top_k=2, num_shared=1,
                            experts_held=4))
