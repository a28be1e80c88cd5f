"""BASELINE.json config 2 proof: GPT-2 125M trains end-to-end on TPU
(data-parallel over the chips this process owns). Prints one JSON line with
throughput and the loss trajectory; off the chip it fails instead of
shrinking to a host config."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import numpy as np

from ray_tpu.models.configs import gpt2_125m
from ray_tpu.parallel import MeshSpec, RULES_DP, make_mesh
from ray_tpu.train.step import transformer_train_step
from ray_tpu.util.accelerators import peak_flops_per_chip
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu


def main(steps=12, warmup=2):
    enable_compile_cache()
    dev = require_tpu()
    cfg = gpt2_125m(remat=True, remat_policy="dots")
    batch, seq = 8, 512
    mesh = make_mesh(MeshSpec(data=-1), devices=jax.devices())
    ts = transformer_train_step(cfg, mesh, rules=RULES_DP)
    params, opt = ts.init(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    b = ts.shard_batch({"tokens": tokens})

    losses = []
    for _ in range(warmup):
        params, opt, loss = ts.step(params, opt, b)
    losses.append(float(loss))
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt, loss = ts.step(params, opt, b)
    losses.append(float(loss))
    dt = time.perf_counter() - t0
    tok_s = batch * seq * steps / dt
    mfu = (tok_s * cfg.flops_per_token(seq)
           / (peak_flops_per_chip() * jax.device_count()))
    print(json.dumps({
        "metric": "gpt2_125m_e2e",
        "tokens_per_s": round(tok_s, 1),
        "mfu": round(mfu, 4),
        "loss_first": round(losses[0], 4),
        "loss_last": round(losses[-1], 4),
        "learns": losses[-1] < losses[0],
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "num_devices": jax.device_count(),
    }))


if __name__ == "__main__":
    main()
