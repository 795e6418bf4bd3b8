"""Tiny stand-ins for the cells, at the program's ``get_reduced`` widths of
internlm2-1.8b, for the CPU tests."""
import copy
import time
from types import SimpleNamespace

from common import load_config, load_traffic

WIDTHS = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "intermediate_size": 160,
          "vocab_size": 64}


def serve_config() -> dict:
    c = copy.deepcopy(load_config("internlm2-1.8b"))
    c.update(WIDTHS, torch_dtype="float32")
    c["serve"].update(page_size=4, num_pages=64, prefill_bucket=16)
    return c


def serve_traffic() -> dict:
    t = copy.deepcopy(load_traffic("serve-chat"))
    t.update(slots=4, rate_per_s=20.0, lead_s=0.3, tail_s=0.3, trace_s=0.3,
             check_tokens=20)
    t["prompt_len"].update(median=12, min=4, max=40)
    t["output_len"].update(median=5, min=2, max=10)
    return t


def context(config, traffic, seed=7, seconds=1.0, limits=None):
    """A loop context as ``run.py`` builds it, without the chip checks."""
    import jax
    import run
    args = SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    limits = limits or {"served_logit_gap": 1e9,
                        "served_logit_gap_mean": 1e9}
    ctx = run.Context(args, {"name": "tiny", "chips": 1}, config, traffic,
                      jax.devices()[:1], limits)
    ctx.t_process = time.monotonic()
    return ctx
