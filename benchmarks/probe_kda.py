"""Delta-rule core probe on the chip, at the shape of
`qwen3_next_80b_a3b.train_rank16_16k` (q, k [1,16384,16,128], v
[1,16384,32,128], one decay a value head, chunks of 128, bfloat16): do the
scalar-decay kernels of ops/kda.py agree with the rule, and how long does each
body take.

1. `check`: every gradient (q, k, v, g, beta) of the scalar body
   (`kda_chunked_pallas` on g [B,S,H]) and of the broadcast into the
   per-channel body (q, k repeated over their value heads, g over the
   channels, as every rank-3 call ran before PR 42), on bfloat16 operands,
   each against `kda_chunked_xla` on float32 operands at full matmul
   precision; `dg_heads` is dg summed over the tokens of a head (what
   reaches `dt_bias`), where the two bodies differ most.
2. `time`: the forward and the backward `pallas_call` alone, per-channel
   (32 heads, g [1,16384,32*128]) and scalar (16 key heads, g [1,16384,32]),
   each inside a `fori_loop` of 20 on the device (a host loop over calls
   this short measures the host), and the whole `value_and_grad` of both
   ways from the host: the difference is the traffic round the kernels.

One JSON line a case. Off the chip the script fails at once.

    python3 benchmarks/probe_kda.py          # check + time
    python3 benchmarks/probe_kda.py time     # no check
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import kda
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

B, S, HK, HV, D, CHUNK, SUB = 1, 16384, 16, 32, 128, 128, 32
NAMES = ("q", "k", "v", "g", "beta")
REPEAT = 20


def _inputs(seed=0):
    """Operands as the `gdn` mixer hands them over: unit q and k, a decay
    -A softplus(a + dt_bias) with A 1-16 by head, beta a sigmoid."""
    ks = jax.random.split(jax.random.key(seed), 6)
    q = kda.l2_normalize(jax.random.normal(ks[0], (B, S, HK, D)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (B, S, HK, D)))
    v = jax.random.normal(ks[2], (B, S, HV, D))
    A = jnp.exp(jnp.linspace(0.0, 2.77, HV))
    g = -A * jax.nn.softplus(jax.random.normal(ks[3], (B, S, HV)) * 0.25 - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, HV)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (B, S, HV, D))


def _broadcast(body):
    """`body` on the per-channel rule's operands, made outside it."""
    def fn(q, k, v, g, beta, **kw):
        q, k, g = kda._per_channel(q, k, v, g)
        return body(q, k, v, g, beta, **kw)
    return fn


def _grad_fn(body, wo):
    def loss(*a):
        return jnp.sum(body(*a, chunk=CHUNK)[0].astype(jnp.float32) * wo)
    return jax.jit(jax.value_and_grad(loss, argnums=range(5)))


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _ms(fn, args, steps=1, repeats=3):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return best


def _half(args):
    return tuple(a.astype(jnp.bfloat16) if n in "qkv" else a
                 for n, a in zip(NAMES, args))


def check(args, wo):
    with jax.default_matmul_precision("highest"):
        ref = _grad_fn(kda.kda_chunked_xla, wo)(*args)
    out = {"case": "check"}
    for name, body in (("scalar_bf16", kda.kda_chunked_pallas),
                       ("per_channel_bf16",
                        _broadcast(kda.kda_chunked_pallas))):
        fn = _grad_fn(body, wo)
        got = fn(*_half(args))
        out[name] = {n: _rel(a, b) for n, a, b in zip(NAMES, got[1], ref[1])}
        out[name]["dg_heads"] = _rel(got[1][3].sum(1), ref[1][3].sum(1))
        out[name]["fwd_bwd_ms_from_host"] = _ms(fn, _half(args), steps=10)
    print(json.dumps(out), flush=True)
    return all(out["scalar_bf16"][n] < 2.0 * out["per_channel_bf16"][n]
               for n in NAMES)


def kernels_ms(args, wo):
    q, k, v, g, beta = _half(args)
    flat = lambda a: a.reshape(B, S, -1)
    s0, scale = jnp.zeros((B, HV, D, D)), D ** -0.5
    do = flat(wo.astype(jnp.bfloat16))
    per = _broadcast(lambda q, k, v, g, beta: (q, k, v, g, beta))
    for name, ops, fwd_call, bwd_call in (
            ("per_channel", tuple(map(flat, per(q, k, v, g, beta)[:4]))
             + (beta,), kda._kda_fwd_call, kda._kda_bwd_call),
            ("scalar", (flat(q), flat(k), flat(v), g, beta),
             kda._gdn_fwd_call, kda._gdn_bwd_call)):
        tail = (scale, CHUNK, SUB, "probe")
        states, tinv = fwd_call(*ops, s0, *tail)[2:]

        @jax.jit  # every operand an argument: a closed-over array is a constant
        def fwd(q, k, v, g, beta, s0):
            return jax.lax.fori_loop(0, REPEAT, lambda i, v: fwd_call(
                q, k, v, g, beta, s0, *tail)[0], v)

        @jax.jit
        def bwd(do, states, tinv, s0, *ops):
            return jax.lax.fori_loop(0, REPEAT, lambda i, do: bwd_call(
                *ops, states, tinv, do, s0, *tail)[2], do)

        print(json.dumps({"case": "time", "body": name,
                          "fwd_ms": _ms(fwd, ops + (s0,)) / REPEAT,
                          "bwd_ms": _ms(bwd, (do, states, tinv, s0) + ops)
                          / REPEAT}), flush=True)


def main(argv):
    require_tpu()
    enable_compile_cache()
    args, wo = _inputs()
    ok = True
    if not argv or argv[0] == "check":
        ok = check(args, wo)
    if not argv or argv[0] == "time":
        kernels_ms(args, wo)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
