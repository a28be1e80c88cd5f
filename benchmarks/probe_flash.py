"""Flash kernel probe on the chip: does every shape the system gives the
kernel compile under this Mosaic, does it agree with the XLA reference, and
how long does it take.

Shapes: the serving prefill buckets ([1, S, H, D] for S = 8 .. 256, forward
only) and the bench_350m training shape ([8, 1024, 16, 64], forward and
gradient) at each block size. One JSON line per case; a case that fails to
compile or disagrees is reported with its error and makes the exit status 1.
Off the chip the script fails at once.
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

H, D = 16, 64  # bench_350m heads / head_dim
# Flash and reference see the same bf16 inputs; flash rounds P to bf16 before
# the PV matmul and the output to bf16 (ulp 2^-8), so errors relative to the
# largest reference value are a few 2^-8. Computing in a lower precision than
# bf16-in/f32-accumulate would exceed this.
TOL = 2e-2


def _qkv(B, S):
    ks = jax.random.split(jax.random.key(S), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), jnp.bfloat16) for k in ks)


def _time(fn, args, steps=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _max_abs(xs):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)))) for x in xs)


def _loss(fn):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)


def probe(B, S, block, grad):
    q, k, v = _qkv(B, S)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block)
    ref = lambda q, k, v: reference_attention(q, k, v, causal=True)
    row = {"B": B, "S": S, "block": block}
    t0 = time.perf_counter()
    fwd = jax.jit(flash).lower(q, k, v).compile()
    row["compile_s"] = round(time.perf_counter() - t0, 2)
    if "tpu_custom_call" not in fwd.as_text():
        raise AssertionError("no tpu_custom_call in the compiled forward")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(q, k, v)
    row["fwd_rel_err"] = round(
        _max_err(fwd(q, k, v), want) / _max_abs([want]), 5)
    row["fwd_ms"] = round(_time(fwd, (q, k, v)), 3)
    if grad:
        t0 = time.perf_counter()
        g = jax.jit(jax.grad(_loss(flash), (0, 1, 2))).lower(q, k, v).compile()
        row["grad_compile_s"] = round(time.perf_counter() - t0, 2)
        with jax.default_matmul_precision("highest"):
            gw = jax.jit(jax.grad(_loss(ref), (0, 1, 2)))(q, k, v)
        row["grad_rel_err"] = round(
            max(_max_err(a, b) for a, b in zip(g(q, k, v), gw))
            / _max_abs(gw), 5)
        row["grad_ms"] = round(_time(g, (q, k, v)), 3)
    ok = row["fwd_rel_err"] < TOL and row.get("grad_rel_err", 0.0) < TOL
    return row, ok


def main() -> int:
    dev = require_tpu()
    enable_compile_cache()
    print(json.dumps({"device_kind": dev.device_kind,
                      "devices": len(jax.devices())}), flush=True)
    cases = [(1, S, 512, False) for S in (8, 16, 32, 64, 128, 256)]
    cases += [(8, 1024, b, True) for b in (128, 256, 512)]
    failed = 0
    for B, S, block, grad in cases:
        try:
            row, ok = probe(B, S, block, grad)
        except Exception as e:  # report every shape, fail at the end
            row, ok = {"B": B, "S": S, "block": block,
                       "error": f"{type(e).__name__}: {e}"[:1500]}, False
        row["ok"] = ok
        failed += not ok
        print(json.dumps(row), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
