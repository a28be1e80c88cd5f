"""Gated short convolution probe on the chip, at the shape of
`lfm2_8b_a1b.train_rank4_8k` (the joint projection's output [4, 8192, 6144]
= [Bg ; Cg ; x] bfloat16, 3 taps over 2,048 channels): XLA's formulation of
y = Cg * conv3(Bg * x) (`shortconv.gated_conv_xla`, the three lines a mixer
would write out) against the Pallas pair (`shortconv.gated_conv_pallas`),
the forward and the forward + backward, `CALLS` independent calls in one
program (a loop that carries p pays a copy of it a turn), each with the
bytes it has to move over the chip's HBM rate beside it (forward 16 KB a
token: 12 read, 4 written; backward 28 KB: 16 read, 12 written); `rel_*` is
each body's result on bfloat16 operands against the XLA body on float32
operands (value, dp, dw). `sweep` times the pair at other row blocks too.

One JSON line a case. Off the chip the script fails at once.

    python3 benchmarks/probe_shortconv.py [sweep]
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import shortconv as sc
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

B, S, C, K = 4, 8192, 2048, 3
CALLS = 4
HBM_BYTES_PER_S = 819e9   # chipbench/peaks.json
ROW_BLOCKS = ((256, 256), (256, 128), (1024, 512), (512, 512), (128, 128))


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _ms(fn, args, repeats=3):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def _pair(body, p, w, dy):
    y, vjp = jax.vjp(body, p, w)
    return (y,) + vjp(dy)


def case(check=True):
    ks = jax.random.split(jax.random.key(S + K), 3)
    ps = [jax.random.normal(k, (B, S, 3 * C), jnp.bfloat16)
          for k in jax.random.split(ks[0], CALLS)]
    dys = [jax.random.normal(k, (B, S, C), jnp.bfloat16)
           for k in jax.random.split(ks[1], CALLS)]
    w = jax.random.normal(ks[2], (K, C)) * 0.5
    tokens, item = B * S, 2
    floor = {"fwd": tokens * 4 * C * item / HBM_BYTES_PER_S * 1e3,
             "bwd": tokens * 7 * C * item / HBM_BYTES_PER_S * 1e3}
    out = {"case": "shortconv", "shape": [B, S, 3 * C], "taps": K,
           "rows": list(sc.ROWS), "floor_fwd_ms": floor["fwd"],
           "floor_fwd_bwd_ms": floor["fwd"] + floor["bwd"]}
    if check:
        f32 = lambda a: a.astype(jnp.float32)
        ref = jax.jit(lambda p, w, dy: _pair(sc.gated_conv_xla, p, w, dy))(
            f32(ps[0]), w, f32(dys[0]))
        for name, body in (("xla", sc.gated_conv_xla),
                           ("pallas", sc.gated_conv_pallas)):
            got = jax.jit(lambda p, w, dy: _pair(body, p, w, dy))(
                ps[0], w, dys[0])
            for n, r, g in zip(("y", "dp", "dw"), ref, got):
                out[f"rel_{n}_{name}"] = _rel(g, r)
    for name, body in (("xla", sc.gated_conv_xla),
                       ("pallas", sc.gated_conv_pallas)):
        fwd = jax.jit(lambda ps, w: [body(p, w) for p in ps])
        both = jax.jit(lambda ps, w, dys: [  # y too: a result the program
            _pair(body, p, w, dy) for p, dy in zip(ps, dys)])  # does not
        # return takes its forward kernel out of the program
        out[name + "_fwd_ms"] = _ms(fwd, (ps, w)) / CALLS
        out[name + "_fwd_bwd_ms"] = _ms(both, (ps, w, dys)) / CALLS
    print(json.dumps(out), flush=True)
    return out


def main(argv):
    require_tpu()
    enable_compile_cache()
    case()
    if argv == ["sweep"]:
        for rows in ROW_BLOCKS:
            sc.ROWS = rows
            sc._fwd_call.clear_cache()
            sc._bwd_call.clear_cache()
            try:
                case(check=False)
            except Exception as e:  # a block the compiler refuses
                print(json.dumps({"case": "shortconv", "rows": list(rows),
                                  "error": repr(e)[:300]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
