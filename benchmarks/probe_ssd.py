"""Mamba-2 core probe on the chip, at the shape of
`granite_4_0_h_micro.train_stage_4k` (x [1,4096,64,64], state 128, one group,
chunks of 256): do the Pallas kernels of ops/ssd.py agree with the XLA body,
and how long does each take.

1. `check`: loss and every gradient (x, dt, A, B, C, D) of `ssd_chunked_xla`
   and of `ssd_chunked_pallas` on bfloat16 operands, each against the XLA
   body on float32 operands at full matmul precision; and the kernels on
   float32 operands against the same. The two bfloat16 columns should read
   alike (a few 2^-9): the kernels round where the XLA body rounds.
2. `time`: the two `pallas_call`s alone, forward and backward, for every
   head-block width given (lanes, default 256 512 1024), each inside a
   `fori_loop` of 20 on the device (a host loop over calls this short
   measures the host: 0.4 ms a dispatch on the chip's worker), and the whole
   `value_and_grad` of both bodies from the host for scale.

One JSON line a case. Off the chip the script fails at once.

    python3 benchmarks/probe_ssd.py              # check + time
    python3 benchmarks/probe_ssd.py time 512     # one width, no check
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssd
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

B, S, H, P, G, N, CHUNK = 1, 4096, 64, 64, 1, 128, 256
NAMES = ("x", "dt", "A", "B", "C", "D")
REPEAT = 20


def _inputs():
    k = jax.random.split(jax.random.key(0), 6)
    return dict(
        x=jax.random.normal(k[0], (B, S, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 2.0),
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7)),
        B=jax.random.normal(k[3], (B, S, G, N)) * 0.3,
        C=jax.random.normal(k[4], (B, S, G, N)) * 0.3,
        D=jnp.ones((H,))), jax.random.normal(k[5], (B, S, H, P))


def _grad_fn(body, wy):
    def loss(*a):
        return jnp.sum(body(*a, chunk=CHUNK)[0].astype(jnp.float32) * wy)
    return jax.jit(jax.value_and_grad(loss, argnums=range(6)))


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


def check(c, wy):
    half = {n: a.astype(jnp.bfloat16) if n in "xBC" else a
            for n, a in c.items()}
    with jax.default_matmul_precision("highest"):
        ref = _grad_fn(ssd.ssd_chunked_xla, wy)(*(c[n] for n in NAMES))
        exact = _grad_fn(ssd.ssd_chunked_pallas, wy)(*(c[n] for n in NAMES))
    out = {"case": "check", "pallas_f32": {
        n: _rel(a, b) for n, a, b in zip(NAMES, exact[1], ref[1])}}
    for name, body in (("xla_bf16", ssd.ssd_chunked_xla),
                       ("pallas_bf16", ssd.ssd_chunked_pallas)):
        fn = _grad_fn(body, wy)
        args = tuple(half[n] for n in NAMES)
        got = fn(*args)
        out[name] = {n: _rel(a, b) for n, a, b in zip(NAMES, got[1], ref[1])}
        out[name]["loss"] = abs(float(got[0]) / float(ref[0]) - 1.0)
        out[name]["fwd_bwd_ms_from_host"] = _ms(fn, args, steps=20)
    print(json.dumps(out), flush=True)
    return max(out["pallas_bf16"][n] for n in NAMES) < 2.0 * max(
        out["xla_bf16"][n] for n in NAMES)


def kernels_ms(c, wy, lanes):
    ssd._LANES = lanes
    hb = ssd.heads_per_step(H // G, P)
    mm = jnp.bfloat16
    rows = lambda a: jnp.swapaxes(a, 1, 2).reshape(B, H // hb, hb, S)
    g = jnp.cumsum((c["dt"] * c["A"]).reshape(B, S // CHUNK, CHUNK, H),
                   axis=2).reshape(B, S, H)
    ops = (c["x"].astype(mm).reshape(B, S, H * P),
           c["B"].astype(mm).reshape(B, S, G * N),
           c["C"].astype(mm).reshape(B, S, G * N), rows(g), rows(c["dt"]),
           jnp.ones((1, H * P)), jnp.zeros((B, H * P, N)))
    states = ssd._ssd_fwd_call(*ops, C=CHUNK)[2]
    dy = wy.astype(mm).reshape(B, S, H * P)

    @jax.jit
    def fwd(x, *rest):
        return jax.lax.fori_loop(0, REPEAT, lambda i, x: ssd._ssd_fwd_call(
            x, *rest, C=CHUNK)[0], x)

    @jax.jit
    def bwd(dy, *rest):
        return jax.lax.fori_loop(0, REPEAT, lambda i, dy: ssd._ssd_bwd_call(
            *rest[:6], states, dy, rest[6], C=CHUNK)[0], dy)

    print(json.dumps({"case": "time", "lanes": lanes, "heads_per_step": hb,
                      "fwd_ms": _ms(fwd, ops) / REPEAT,
                      "bwd_ms": _ms(bwd, (dy,) + ops) / REPEAT}), flush=True)


def main(argv):
    require_tpu()
    enable_compile_cache()
    c, wy = _inputs()
    ok = True
    if not argv or argv[0] == "check":
        ok = check(c, wy)
    if not argv or argv[0] == "time":
        for lanes in [int(a) for a in argv[1:]] or [256, 512, 1024]:
            kernels_ms(c, wy, lanes)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
