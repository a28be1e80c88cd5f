"""MFU sweep on one chip: remat policy x batch size, pipelined dispatch (no
per-step host sync), after asserting that the Pallas flash kernel is on the
compiled path. Off the chip it fails at once.

Usage: python benchmarks/mfu_sweep.py [--steps N]
Prints one JSON line per variant; a variant that fails to compile or fit is
reported with its error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from ray_tpu.models.configs import bench_350m
from ray_tpu.parallel import MeshSpec, RULES_DP, make_mesh
from ray_tpu.train.step import transformer_train_step
from ray_tpu.util.accelerators import peak_flops_per_chip
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu


def run_variant(remat, policy, batch, seq, steps, warmup=2, shift=False):
    cfg = bench_350m(remat=remat, remat_policy=policy)
    dev = jax.devices()[0]
    mesh = make_mesh(MeshSpec(), devices=[dev])
    ts = transformer_train_step(cfg, mesh, rules=RULES_DP, shift_inputs=shift)
    params, opt_state = ts.init(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
    )
    b = ts.shard_batch({"tokens": tokens})

    for _ in range(warmup):
        params, opt_state, loss = ts.step(params, opt_state, b)
    float(loss)  # fence warmup

    # Pipelined timing: dispatch every step (each depends on the previous via
    # donated params, so execution is serialized by data flow), fetch ONE
    # scalar at the end. The final D2H blocks until all steps completed.
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = ts.step(params, opt_state, b)
    final = float(loss)
    dt = time.perf_counter() - t0

    tok_s = batch * seq * steps / dt
    mfu = tok_s * cfg.flops_per_token(seq) / peak_flops_per_chip()
    return {
        "remat": remat, "policy": policy if remat else None,
        "batch": batch, "seq": seq, "shift": shift,
        "tok_s": round(tok_s, 1), "mfu": round(mfu, 4),
        "step_ms": round(dt / steps * 1e3, 2), "loss": round(final, 4),
    }


def assert_flash_in_hlo():
    cfg = bench_350m(remat=True, remat_policy="dots")
    mesh = make_mesh(MeshSpec(), devices=[jax.devices()[0]])
    ts = transformer_train_step(cfg, mesh, rules=RULES_DP, shift_inputs=True)
    params, opt_state = ts.init(jax.random.key(0))
    b = ts.shard_batch({"tokens": np.zeros((8, 1025), dtype=np.int32)})
    hlo = ts.lower_step(params, opt_state, b).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise RuntimeError("no tpu_custom_call in the compiled train step")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=15)
    args = ap.parse_args()

    enable_compile_cache()
    print(json.dumps({"device_kind": require_tpu().device_kind}), flush=True)
    assert_flash_in_hlo()

    # (remat, policy, batch, seq, shift)
    variants = [
        (True, "full", 8, 1024, True),
        (True, "dots", 8, 1024, True),        # the default policy
        (False, None, 8, 1024, True),         # no remat
        (True, "dots", 16, 1024, True),       # bigger matmul M
    ]
    for remat, policy, batch, seq, shift in variants:
        try:
            r = run_variant(remat, policy, batch, seq, args.steps,
                            shift=shift)
        except Exception as e:
            r = {"remat": remat, "policy": policy, "batch": batch, "seq": seq,
                 "shift": shift, "error": str(e)[:300]}
        print(json.dumps(r), flush=True)
