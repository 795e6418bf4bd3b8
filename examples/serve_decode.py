"""Serving example on the continuous-batching engine (repro.serve): a
stream of variable-length requests is packed into a fixed-slot batch with a
slot-paged, optionally int8-quantized KV-cache pool — and, for SSM/hybrid
archs, a slot-indexed quantized recurrent-state cache (attention sublayers
hit the KV pool, SSM/RWKV sublayers hit the state cache; one engine serves
every decoder family in the zoo):

    PYTHONPATH=src python examples/serve_decode.py --arch internlm2-1.8b --quantized --fused
    PYTHONPATH=src python examples/serve_decode.py --arch internlm2-1.8b --reduced
    PYTHONPATH=src python examples/serve_decode.py --arch deepseek-v2-236b --reduced --temperature 0.8
    PYTHONPATH=src python examples/serve_decode.py --arch rwkv6-1.6b --reduced --quantized
    PYTHONPATH=src python examples/serve_decode.py --arch jamba-1.5-large --reduced

Published widths in the config's dtype by default (sized for a chip);
``--reduced`` runs the f32 toy widths of ``get_reduced`` (the CPU size).
"""
import argparse
import json
import time

import numpy as np
import jax

from repro.models import build_lm, init_lm
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import get_model_cfg
from repro.serve import (Engine, EngineConfig, PoolConfig, SamplingParams)
from repro.sharding import ShardPlan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="get_reduced toy widths in f32 (default: published "
                         "widths in the config's dtype)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--quantized", action="store_true",
                    help="int8 pow-2 KV-cache pool + recurrent-state cache "
                         "(fp storage otherwise)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--fused", action="store_true",
                    help="fused paged-attention decode (per-page in-kernel "
                         "dequant; MLA sublayers take the latent walk)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg, _ = get_model_cfg(args.arch, args.reduced)
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only — no decode path")
    if cfg.frontend != "none":
        raise SystemExit(f"{args.arch}: frontend (vision/audio) serving is "
                         f"an open roadmap item")
    plan = ShardPlan(mesh=None)
    lm = build_lm(cfg)
    params = init_lm(jax.random.PRNGKey(0), lm)

    horizon = args.prompt_len + args.gen_len
    pcfg = PoolConfig(
        num_slots=args.slots, page_size=args.page_size,
        pages_per_slot=-(-horizon // args.page_size) + 1,
        quantized=args.quantized)
    eng = Engine(lm, params,
                 EngineConfig(pool=pcfg, prefill_chunk=args.prefill_chunk,
                              fused_attention=args.fused),
                 plan)
    sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                        top_p=args.top_p)

    rng = np.random.RandomState(1)
    rids = []
    for i in range(args.requests):
        # variable-length prompts: 1/2..1x of --prompt-len
        plen = int(rng.randint(max(args.prompt_len // 2, 1),
                               args.prompt_len + 1))
        prompt = rng.randint(0, cfg.vocab_size, plen).tolist()
        rids.append(eng.submit(prompt, max_new_tokens=args.gen_len,
                               sampling=sp))

    t0 = time.time()
    results = eng.run()
    dt = time.time() - t0
    s = eng.summary()
    mode = "int8" if args.quantized else "fp"
    # only report the pools this arch actually allocates: pure-SSM archs
    # have no KV pool (and run unpaged), attn-only archs no state cache
    pools = []
    if s["cache_bytes"]:
        pools.append(f"kv cache {s['cache_bytes']/1024:.0f} KiB "
                     f"({s['cache_reduction']:.1f}x vs fp32)")
    if s["state_bytes"]:
        pools.append(f"state cache {s['state_bytes']/1024:.0f} KiB "
                     f"({s['state_reduction']:.1f}x vs fp32)")
    label = f"{mode}-paged" if s["cache_bytes"] else f"{mode}-state"
    print(f"served {s['requests_completed']} requests "
          f"({s['generated_tokens']} tokens) on {args.slots} slots "
          f"[{label}] in {dt:.2f}s — {s['tokens_per_s']:.0f} tok/s, "
          f"ttft p50 {s['ttft_p50_s']*1e3:.0f}ms, "
          + ", ".join(pools))
    print("sample:", results[rids[0]].tokens[:16])
    print(json.dumps(s, indent=2))


if __name__ == "__main__":
    main()
