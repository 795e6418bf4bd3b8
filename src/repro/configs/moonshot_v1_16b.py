"""moonshot-v1-16b [moe]: a GQA, all-MoE stand-in of the zoo, not
Moonlight: 48L d_model=2048 16H (GQA kv=16) expert d_ff=1408 vocab=163840,
MoE 64e top-6 with softmax routing and capacity drops, no shared experts
and no dense layer. Moonlight-16B-A3B itself (27 layers, MLA, 2 shared
experts, a dense layer 0, sigmoid routing) is ``moonlight_16b_a3b.py``.
Its numbers stay as they are: tests/test_prefix_cache.py serves them.
The 163,840-row embedding is the zoo's biggest TTM win when --tt is on."""
from .base import MoEConfig, ModelConfig

CONFIG = ModelConfig(
    name="moonshot-v1-16b", family="moe", num_layers=48, d_model=2048,
    num_heads=16, num_kv_heads=16, d_ff=1408, vocab_size=163840,
    moe=MoEConfig(num_experts=64, top_k=6),
)
STRATEGY = "tp"

REDUCED = CONFIG.replace(num_layers=2, d_model=64, num_heads=4,
                         num_kv_heads=4, d_ff=96, vocab_size=128,
                         moe=MoEConfig(num_experts=8, top_k=2))
