"""Single-chip autoregressive decode throughput on the 350M flagship.

Prints one JSON line: tokens/s of generated (decode-phase) tokens plus the
prefill time, batch 8 / prompt 128 / 128 new tokens by default. The whole
generation is one compiled program (models/generate.py lax.scan), so the
measurement is dominated by steady-state per-token latency — the
memory-bandwidth-bound regime decoding lives in (each step reads every
parameter once: ~0.7GB at 350M bf16, so the roofline is HBM, not MXU).

Runs on the chip only: off it, the script fails instead of timing the host.

Usage: python benchmarks/decode_bench.py [--batch 8 --prompt 128 --new 128]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new", type=int, default=128)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--int8", action="store_true",
                    help="weight-only int8 layer weights "
                         "(models/quantize.py): ~halves the bytes each "
                         "decode step streams from HBM")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import generate
    from ray_tpu.models import transformer as tfm
    from ray_tpu.models.configs import bench_350m
    from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

    enable_compile_cache()
    dev = require_tpu()
    cfg = bench_350m(remat=False)
    params = tfm.init_params(jax.random.key(0), cfg)
    if args.int8:
        from ray_tpu.models.quantize import quantize_params_int8

        params = quantize_params_int8(params)
    params = jax.device_put(params)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (args.batch, args.prompt), np.int32))

    gen = jax.jit(lambda p, t, r: generate(
        p, t, cfg, max_new_tokens=args.new, temperature=0.0, rng=r))
    out = gen(params, tokens, jax.random.key(1))
    out.block_until_ready()  # compile + warm

    best = float("inf")
    for i in range(args.reps):
        t0 = time.perf_counter()
        out = gen(params, tokens, jax.random.key(2 + i))
        out.block_until_ready()
        best = min(best, time.perf_counter() - t0)
    new_tokens = args.batch * args.new
    # Rough split: one extra prefill-only call times the prompt phase.
    pre = jax.jit(lambda p, t: generate(p, t, cfg, max_new_tokens=1))
    pre(params, tokens).block_until_ready()
    t0 = time.perf_counter()
    pre(params, tokens).block_until_ready()
    prefill_s = time.perf_counter() - t0
    decode_s = max(best - prefill_s, 1e-9)
    print(json.dumps({
        "metric": "decode_tokens_per_s_350m",
        "batch": args.batch, "prompt": args.prompt, "new": args.new,
        "tokens_per_s": round(new_tokens / best, 1),
        "decode_tokens_per_s": round(new_tokens / decode_s, 1),
        "per_token_ms": round(decode_s / args.new * 1e3, 3),
        "prefill_ms": round(prefill_s * 1e3, 1),
        "wall_s": round(best, 3),
        "int8": args.int8,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
    }), flush=True)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:
        print(json.dumps({"error": str(e)[:300],
                          "argv": sys.argv[1:]}), flush=True)
        sys.exit(1)
