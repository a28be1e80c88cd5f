"""Flash kernel probe on the chip: does every shape the system gives the
kernel compile under this Mosaic, does it agree with the XLA reference, and
how long does each kernel take at each tile size.

Shapes: the serving prefill buckets ([1, S, 16, 128] for S = 8 .. 2048,
forward only, the table's tiles) and the three training cells' shapes
(`CELLS`: forward and gradient against the reference at the table's tiles,
then forward, the fused backward, dQ and dK/dV timed one by one for every
(block, strip) of `SWEEP`). The table `_TILES` of ops/flash_attention.py is filled from the
sweep's lines. One JSON line per case; a case that fails to compile or
disagrees is reported with its error and makes the exit status 1. Off the
chip the script fails at once.

    python3 benchmarks/probe_flash.py            # check + sweep
    python3 benchmarks/probe_flash.py check      # check only
    python3 benchmarks/probe_flash.py band       # the windowed calls only:
        # `BAND` checked against the reference at a length its [H, S, S]
        # fits, at the table's tiles and at every (block, strip) of
        # `BAND_SWEEP`, then timed at the cell's length beside the same
        # shape's full causal call (the sweep beside `_TILES`)
    python3 benchmarks/probe_flash.py band512    # the same for `BAND512`
        # (a window a quarter of the table's block, groups of 9), and the
        # full layers' causal call beside it at every (block, strip)
    python3 benchmarks/probe_flash.py fused [cell ...]   # `FUSED`: each
        # cell's attention kinds checked against the reference with the
        # fused backward, then forward / fused backward / dQ / dK/dV timed
        # at the table's row and its neighbours
    python3 benchmarks/probe_flash.py gated      # `GATED`, heads of 256 at
        # 8:1: checked at GATED_CHECK_S positions at the table's tiles and
        # at every (block, strip) of `GATED_SWEEP`, then timed at 16,384
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import reference_attention
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

# (name, B, S, H, KVH, D, Dv): what `attention()` hands the kernels in
# gpt2_124m.train_1chip, internlm2_1_8b.train_mesh4 (a device's shard) and
# kimi_linear_48b_a3b.train_share_8k (MLA).
CELLS = [("gpt2", 16, 1024, 12, 12, 64, 64),
         ("internlm2_shard", 4, 2048, 8, 4, 128, 128),
         ("kimi_mla", 1, 8192, 32, 32, 192, 128)]
# (name, B, S, H, KVH, D, Dv, window): mellum2_12b_a2_5b.train_share_16k's
# sliding layers; checked at BAND_CHECK_S positions (three windows).
BAND = ("mellum2_swa", 1, 16384, 32, 4, 128, 128, 1024)
BAND_CHECK_S = 4096
BAND_SWEEP = [(512, 128), (512, 256), (1024, 128), (1024, 256), (1024, 512),
              (2048, 256), (2048, 512)]
# laguna_s_2_1.train_rank32_8k: the sliding layers (36 query heads over 4 key
# heads, window 512) and, swept beside them, the full layers' causal call at
# 24 query heads; checked at 2,048 positions (four windows).
BAND512 = ("laguna_swa", 1, 8192, 36, 4, 128, 128, 512)
BAND512_FULL_H = 24
BAND512_CHECK_S = 2048
BAND512_SWEEP = [(b, s) for b in (512, 1024, 2048) for s in (128, 256)]
# (name, B, S, H, KVH, D, Dv): qwen3_next_80b_a3b.train_rank16_16k's gated
# attention layer (K and V of a block are twice the bytes of the 128 row).
GATED = ("qwen3_next_gattn", 1, 16384, 16, 2, 256, 256)
GATED_CHECK_S = 4096
GATED_SWEEP = [(512, 128), (512, 256), (512, 512), (1024, 128), (1024, 256),
               (1024, 512), (2048, 128), (2048, 256), (2048, 512)]
# (name, B, S, H, KVH, D, Dv, window, check S, [(block, strip)]): every
# cell's attention kinds, the fused backward beside the pair at the table's
# row (first) and at its neighbours.
FUSED = [
    ("kanana_mla", 1, 16384, 32, 32, 192, 128, None, 4096,
     [(1024, 256), (1024, 128), (1024, 512), (512, 256), (2048, 256)]),
    ("mellum2_full", 1, 16384, 32, 4, 128, 128, None, 4096,
     [(2048, 256), (1024, 256), (2048, 512), (2048, 128)]),
    ("mellum2_swa", 1, 16384, 32, 4, 128, 128, 1024, 4096,
     [(2048, 256), (1024, 256), (2048, 128), (1024, 128)]),
    ("qwen3_next_gattn", 1, 16384, 16, 2, 256, 256, None, 4096,
     [(1024, 512), (1024, 256), (512, 256), (512, 512)]),
    ("laguna_swa", 1, 8192, 36, 4, 128, 128, 512, 2048,
     [(2048, 256), (2048, 128), (1024, 128), (1024, 256)]),
    ("laguna_full", 1, 8192, 24, 4, 128, 128, None, 2048,
     [(2048, 256), (1024, 256)]),
    ("gpt2", 16, 1024, 12, 12, 64, 64, None, 1024,
     [(1024, 256), (1024, 128), (512, 256), (1024, 512)]),
    ("granite_gqa64", 1, 4096, 32, 8, 64, 64, None, 4096,
     [(1024, 256), (1024, 128)]),
    ("internlm2_shard", 4, 2048, 8, 4, 128, 128, None, 2048,
     [(2048, 256), (1024, 256), (2048, 512)]),
    ("kimi_mla", 1, 8192, 32, 32, 192, 128, None, 4096, [(1024, 256)]),
]
SWEEP = [(b, s) for b in (512, 1024, 2048, 4096) for s in (128, 256, 512)]
SWEEP += [(b, b) for b in (512, 1024)]  # no strips: the split of tiles alone
# Flash and reference see the same bf16 inputs; flash rounds P to bf16 before
# the PV matmul and the output to bf16 (ulp 2^-8), so errors relative to the
# largest reference value are a few 2^-8. Computing in a lower precision than
# bf16-in/f32-accumulate would exceed this.
TOL = 2e-2
CHECK_HEADS = 4  # the reference holds [B, H, S, S] in float32


def _qkv(B, S, H, KVH, D, Dv):
    ks = jax.random.split(jax.random.key(S + D), 3)
    return (jax.random.normal(ks[0], (B, S, H, D), jnp.bfloat16),
            jax.random.normal(ks[1], (B, S, KVH, D), jnp.bfloat16),
            jax.random.normal(ks[2], (B, S, KVH, Dv), jnp.bfloat16))


def _time(fn, args, steps=30, repeats=3):
    """ms a call: the least of `repeats` means over `steps` calls in flight."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return best


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _max_abs(xs):
    return max(float(jnp.max(jnp.abs(x.astype(jnp.float32)))) for x in xs)


def _loss(fn):
    return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)


def check(B, S, H, KVH, D, Dv, grad, window=None, block=None, sub=None):
    """The table's tiles (or the named ones) against the reference, forward
    and gradient."""
    q, k, v = _qkv(B, S, H, KVH, D, Dv)
    flash = lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window, block_q=block, block_k=block,
        sub=sub)
    ref = lambda q, k, v: reference_attention(q, k, v, causal=True,
                                              window=window)
    row = {"check": [B, S, H, KVH, D, Dv],
           "tiles": fa.tile_sizes(S, D, Dv, q.dtype, block, block, sub)}
    if window is not None:
        row["window"] = window
    t0 = time.perf_counter()
    fwd = jax.jit(flash).lower(q, k, v).compile()
    row["compile_s"] = round(time.perf_counter() - t0, 2)
    if "tpu_custom_call" not in fwd.as_text():
        raise AssertionError("no tpu_custom_call in the compiled forward")
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref)(q, k, v)
    row["fwd_rel_err"] = round(
        _max_err(fwd(q, k, v), want) / _max_abs([want]), 5)
    if grad:
        t0 = time.perf_counter()
        g = jax.jit(jax.grad(_loss(flash), (0, 1, 2))).lower(q, k, v).compile()
        row["grad_compile_s"] = round(time.perf_counter() - t0, 2)
        with jax.default_matmul_precision("highest"):
            gw = jax.jit(jax.grad(_loss(ref), (0, 1, 2)))(q, k, v)
        row["grad_rel_err"] = round(
            max(_max_err(a, b) for a, b in zip(g(q, k, v), gw))
            / _max_abs(gw), 5)
    ok = row["fwd_rel_err"] < TOL and row.get("grad_rel_err", 0.0) < TOL
    return row, ok


def kernel_ms(B, S, H, KVH, D, Dv, block, sub, window=None, steps=30):
    """Forward, the fused backward, and the pair it replaces, dQ and dK/dV
    alone (XLA drops the kernel whose results a program does not return;
    a budget of 0 sends any shape to the pair), in model layout's
    transposed form."""
    q, k, v = (jnp.swapaxes(x, 1, 2) for x in _qkv(B, S, H, KVH, D, Dv))
    scale = D ** -0.5
    tiles = dict(block_q=block, block_k=block, sub=sub, window=window)
    fwd = jax.jit(lambda q, k, v: fa._flash_fwd(q, k, v, scale, True, **tiles))
    o, lse = fwd(q, k, v)
    do = jnp.ones_like(o)
    delta = jnp.sum(o.astype(jnp.float32), axis=-1)
    args = (q, k, v, do, lse, delta)

    def bwd(pick, budget):
        was, fa.FUSED_DQ_VMEM_BUDGET = fa.FUSED_DQ_VMEM_BUDGET, budget
        try:  # the rule is read where the call is traced
            return jax.jit(lambda *a: pick(fa.flash_bwd_core(
                *a, scale=scale, causal=True, **tiles))).lower(
                    *args).compile()
        finally:
            fa.FUSED_DQ_VMEM_BUDGET = was

    row = {"bwd": fa.bwd_kind(S, D, Dv, q.dtype, block, block, sub),
           "fwd_ms": round(_time(fwd, (q, k, v), steps), 4)}
    if row["bwd"] == "fused":
        row["bwd_fused_ms"] = round(_time(
            bwd(lambda g: g, fa.FUSED_DQ_VMEM_BUDGET), args, steps), 4)
    row["dq_ms"] = round(_time(bwd(lambda g: g[0], 0), args, steps), 4)
    row["dkv_ms"] = round(_time(bwd(lambda g: g[1:], 0), args, steps), 4)
    return row


def main(argv) -> int:
    dev = require_tpu()
    enable_compile_cache()
    print(json.dumps({"device_kind": dev.device_kind,
                      "devices": len(jax.devices())}), flush=True)
    failed = 0

    def report(row, fn, *args):
        nonlocal failed
        try:
            got, ok = fn(*args)
            row.update(got)
        except Exception as e:  # report every case, fail at the end
            row["error"], ok = f"{type(e).__name__}: {e}"[:600], False
        row["ok"] = ok
        failed += not ok
        print(json.dumps(row), flush=True)

    if argv[1:] in (["band"], ["band512"]):
        # (the band, its check length, its sweep, the full layer's heads and
        # its (block, strip)s)
        band, check_s, sweep, full_h, full = {
            "band": (BAND, BAND_CHECK_S, BAND_SWEEP, BAND[3],
                     ((1024, 256), (2048, 256))),
            "band512": (BAND512, BAND512_CHECK_S, BAND512_SWEEP,
                        BAND512_FULL_H, BAND512_SWEEP)}[argv[1]]
        name, B, S, H, KVH, D, Dv, window = band
        h = 2 * H // KVH  # two key/value heads with all their query heads
        for block, sub in [(None, None)] + sweep:
            report({"cell": name, "block": block, "sub": sub}, check,
                   B, check_s, h, 2, D, Dv, True, window, block, sub)
        for block, sub in sweep:
            report({"cell": name, "block": block, "sub": sub,
                    "window": window},
                   lambda *a: (kernel_ms(*a), True),
                   B, S, H, KVH, D, Dv, block, sub, window)
        for block, sub in full:  # the full layer
            report({"cell": name, "block": block, "sub": sub, "window": None},
                   lambda *a: (kernel_ms(*a), True),
                   B, S, full_h, KVH, D, Dv, block, sub)
        return 1 if failed else 0
    if argv[1:2] == ["fused"]:
        for name, B, S, H, KVH, D, Dv, window, check_s, sweep in FUSED:
            if argv[2:] and name not in argv[2:]:
                continue
            h = 2 * H // KVH if H > KVH else CHECK_HEADS
            report({"cell": name, "bwd": fa.bwd_kind(check_s, D, Dv,
                                                     jnp.bfloat16)},
                   check, 1, check_s, h, h * KVH // H, D, Dv, True, window)
            for block, sub in sweep:
                report({"cell": name, "block": block, "sub": sub,
                        "window": window},
                       lambda *a: (kernel_ms(*a, steps=10), True),
                       B, S, H, KVH, D, Dv, block, sub, window)
        return 1 if failed else 0
    if argv[1:] == ["gated"]:
        name, B, S, H, KVH, D, Dv = GATED
        for block, sub in [(None, None)] + GATED_SWEEP:
            report({"cell": name, "block": block, "sub": sub}, check,
                   B, GATED_CHECK_S, H // KVH, 1, D, Dv, True, None, block,
                   sub)
        for block, sub in GATED_SWEEP:
            report({"cell": name, "block": block, "sub": sub},
                   lambda *a: (kernel_ms(*a), True),
                   B, S, H, KVH, D, Dv, block, sub)
        return 1 if failed else 0
    for S in (8, 16, 32, 64, 128, 256, 512, 1024, 2048):
        report({}, check, 1, S, 16, 8, 128, 128, False)
    for name, B, S, H, KVH, D, Dv in CELLS:
        h = min(H, CHECK_HEADS)
        report({"cell": name}, check, B if S < 8192 else 1, S, h,
               max(1, h * KVH // H), D, Dv, True)
    if argv[1:] == ["check"]:
        return 1 if failed else 0
    for name, B, S, H, KVH, D, Dv in CELLS:
        for block, sub in SWEEP:
            if block > S:
                continue
            report({"cell": name, "block": block, "sub": sub},
                   lambda *a: (kernel_ms(*a), True),
                   B, S, H, KVH, D, Dv, block, sub)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
