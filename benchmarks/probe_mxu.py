"""True MXU ceiling: K chained matmuls inside ONE jitted program (zero
dispatch overhead, data-dependent so nothing is elided). Chip only."""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp
from jax import lax


def probe(n, inner=20, reps=3):
    k = jax.random.key(0)
    a = jax.random.normal(k, (n, n), jnp.bfloat16)
    b = jax.random.normal(k, (n, n), jnp.bfloat16)

    @jax.jit
    def chain(a, b):
        def body(i, x):
            y = x @ b
            # keep magnitude bounded so bf16 doesn't overflow to inf
            return y * jnp.bfloat16(1.0 / n)

        return lax.fori_loop(0, inner, body, a)

    c = chain(a, b)
    c.block_until_ready()
    float(jnp.sum(c.astype(jnp.float32)))
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        c = chain(a, b)
        float(jnp.sum(c.astype(jnp.float32)))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    fl = 2 * n**3 * inner
    return {"probe": f"chain_matmul{n}x{inner}",
            "tflops": round(fl / best / 1e12, 1),
            "ms_total": round(best * 1e3, 2)}


if __name__ == "__main__":
    from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

    enable_compile_cache()
    print(json.dumps({"device_kind": require_tpu().device_kind}), flush=True)
    for n in (2048, 4096, 8192):
        try:
            print(json.dumps(probe(n)), flush=True)
        except Exception as e:
            print(json.dumps({"n": n, "error": repr(e)[:200]}), flush=True)
    # bench-relevant shape: [8192, 1024] x [1024, 4096] style MLP matmul
    import numpy as np

    k = jax.random.key(1)
    x = jax.random.normal(k, (8192, 1024), jnp.bfloat16)
    w = jax.random.normal(k, (1024, 2816), jnp.bfloat16)

    @jax.jit
    def mlp_chain(x, w):
        def body(i, acc):
            h = acc @ w          # [8192, 2816]
            acc2 = h @ w.T       # [8192, 1024]
            return acc2 * jnp.bfloat16(1e-3)

        return jax.lax.fori_loop(0, 20, body, x)

    y = mlp_chain(x, w)
    float(jnp.sum(y.astype(jnp.float32)))
    t0 = time.perf_counter()
    y = mlp_chain(x, w)
    float(jnp.sum(y.astype(jnp.float32)))
    dt = time.perf_counter() - t0
    fl = 2 * 8192 * 1024 * 2816 * 2 * 20
    print(json.dumps({"probe": "mlp_shape_chain", "tflops": round(fl / dt / 1e12, 1),
                      "ms_total": round(dt * 1e3, 2)}), flush=True)
