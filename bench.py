"""Benchmark: single-chip training throughput + MFU of the flagship decoder.

Runs on the chip and prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline", ...}, or exits non-zero with the error. Off the chip it fails
at once; there is no host fallback and no stored number to fall back to.
value = tokens/sec/chip on a llama-family ~350M model, bf16 activations,
adamw. vs_baseline = achieved MFU / 0.45 (the Llama north-star MFU target
from BASELINE.json; the reference publishes no tokens/sec numbers —
BASELINE.md). One process: it owns the chip for its lifetime.
"""
from __future__ import annotations

import json
import time


def main() -> None:
    from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

    enable_compile_cache()
    dev = require_tpu()
    import jax
    import numpy as np

    from ray_tpu.models.configs import bench_350m
    from ray_tpu.parallel import MeshSpec, RULES_DP, make_mesh
    from ray_tpu.train.step import transformer_train_step
    from ray_tpu.util.accelerators import peak_flops_per_chip

    # shift_inputs runs the model at the aligned power-of-two length S
    # instead of S+1 (models/transformer.py loss_fn).
    cfg = bench_350m(remat=True, remat_policy="dots")
    batch, seq = 8, 1024
    steps, warmup = 20, 3

    mesh = make_mesh(MeshSpec(), devices=[dev])
    ts = transformer_train_step(cfg, mesh, rules=RULES_DP, shift_inputs=True)
    params, opt_state = ts.init(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
    )
    b = ts.shard_batch({"tokens": tokens})

    # Pallas kernels lower to custom_call_target="tpu_custom_call"; a
    # generic "custom-call" match would also hit unrelated runtime calls.
    hlo = ts.lower_step(params, opt_state, b).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise RuntimeError(
            "the compiled train step has no tpu_custom_call: the flash "
            "attention kernel is not on the path")

    for _ in range(warmup):
        params, opt_state, loss = ts.step(params, opt_state, b)
    float(loss)  # fence warmup

    # Every step depends on the previous via donated params, so execution
    # is serialized by data flow; one scalar D2H at the end blocks until
    # all steps completed.
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = ts.step(params, opt_state, b)
    float(loss)
    dt = time.perf_counter() - t0

    tok_s = batch * seq * steps / dt
    mfu = tok_s * cfg.flops_per_token(seq) / peak_flops_per_chip()
    print(
        json.dumps(
            {
                "metric": "train_tokens_per_sec_per_chip_350m",
                "value": round(tok_s, 1),
                "unit": "tokens/s",
                "vs_baseline": round(mfu / 0.45, 4),
                "mfu": round(mfu, 4),
                "model_params": cfg.num_params(),
                "platform": dev.platform,
                "device_kind": dev.device_kind,
                "flash_in_hlo": True,
            }
        )
    )


if __name__ == "__main__":
    main()
