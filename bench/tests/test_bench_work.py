"""The yardstick's arithmetic against hand counts at the program's
``get_reduced`` widths of internlm2-1.8b (2 layers, d=64, 4 heads, 2 KV
heads, head 16, ffn 160, vocab 64), reached through ``work.py``'s dispatch
to the configuration's layout, and the peak table."""
import pytest

import peaks
import work
from tiny import WIDTHS

C = {**WIDTHS, "torch_dtype": "float32",
     "program": {"layout": "dense_gqa"}}


def test_dense_counts():
    # q, kv, o: 64x64 each; gate, up, down: 160x64 each
    assert work.layer_params(C) == 3 * 4096 + 3 * 10240
    assert work.head_params(C) == 4096
    # 10 keys: 2 layers x (scores + values) x 2 x 4 heads x 16 x 10
    assert work.attention_flops(C, 10) == 5120
    assert work.decode_token_flops(C, 10) == 2 * (2 * 43008 + 4096) + 5120
    # per layer: K+V 2 x 10 x 2 x 16 x 1 B, two f32 scales, q and out bf16
    assert work.paged_attention_bytes(C, 10, 1, 2) == 2 * (640 + 8 + 256)


def test_peaks_known_and_unknown():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
