#!/usr/bin/env python3
"""Chip probe of ops/sparse_attention.py at the Keye-VL-2.0 cell's shape
([1, S, 32|4, 128], an indexer of 16 x 64, top 2,048): each kernel's ms a
call, the core's two kernels over a few tiles, and the selection's count to
the unit; and, with `gathered` as a second argument, ISSUE 54's other way
alone: attention over kept keys brought by index through XLA's gather, on
2,048 late query rows (a sixteenth of the layer). (What the kernels compute is held to the plain reference by the
cell's own comparison, chipbench/drivers/train_stack_sparse.py, at the cell's
size; tests/test_dsa.py at a small one.) Refuses to run off the chip.

    chiprun -- python3 benchmarks/probe_dsa.py [S] [gathered]
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from ray_tpu.ops import sparse_attention as sa


def inputs(key, B, S, H, KVH, D, HI, dI, dtype):
    ks = jax.random.split(key, 7)
    n = lambda k, sh: jax.random.normal(k, sh, jnp.float32)
    return (n(ks[0], (B, S, H, D)).astype(dtype),
            n(ks[1], (B, S, KVH, D)).astype(dtype),
            n(ks[2], (B, S, KVH, D)).astype(dtype),
            n(ks[3], (B, S, HI, dI)).astype(dtype),
            n(ks[4], (B, S, dI)).astype(dtype),
            n(ks[5], (B, S, HI)) / (HI * dI) ** 0.5,
            n(ks[6], (B, S, H, D)).astype(dtype))


def timed(fn, *args, n=5):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t) / n * 1e3, out


def gathered(S, H, KVH, D, topk, scale, rows=2048, block=64):
    """The gathered way (ISSUE 54's (a)) in XLA: a block of `block` queries
    brings its rows' `topk` kept keys and values by index ([block, topk,
    KVH, D] each) and attends to them, products G = H / KVH rows tall.
    Forward and forward + backward ms over `rows` late queries (random
    sorted indices: the time does not depend on which keys are kept)."""
    ks = jax.random.split(jax.random.key(7), 5)
    n = lambda k, sh: jax.random.normal(k, sh, jnp.float32).astype(
        jnp.bfloat16)
    q, co = n(ks[0], (rows, H, D)), n(ks[1], (rows, H, D))
    k, v = n(ks[2], (S, KVH, D)), n(ks[3], (S, KVH, D))
    idx = jnp.sort(jax.random.randint(ks[4], (rows, topk), 0, S - rows), -1)
    G = H // KVH

    def attend(q, k, v):
        def one(args):
            qb, ib = args
            kg, vg = k[ib], v[ib]                        # [block,topk,KVH,D]
            s = jnp.einsum("rngd,rknd->rngk", qb.reshape(block, KVH, G, D),
                           kg, preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(s, -1).astype(v.dtype)
            return jnp.einsum("rngk,rknd->rngd", p, vg).reshape(block, H, D)
        return jax.lax.map(jax.checkpoint(one), (
            q.reshape(-1, block, H, D), idx.reshape(-1, block, topk)))

    fwd, _ = timed(jax.jit(attend), q, k, v, n=3)
    both, _ = timed(jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).reshape(rows, H, D).astype(
            jnp.float32) * co), argnums=(0, 1, 2))), q, k, v, n=3)
    return {"rows": rows, "block": block, "fwd_ms": fwd,
            "fwd_bwd_ms": both, "layer_fwd_ms": fwd * S / rows,
            "layer_fwd_bwd_ms": both * S / rows}


def main():
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("probe_dsa: no TPU; this probe measures the chip")
    S = int(sys.argv[1]) if len(sys.argv) > 1 else 32768
    B, H, KVH, D, HI, dI, topk = 1, 32, 4, 128, 16, 64, 2048
    scale = D ** -0.5
    out = {"device": jax.devices()[0].device_kind, "S": S}
    if sys.argv[2:] == ["gathered"]:
        out["gathered"] = gathered(S, H, KVH, D, topk, scale)
        print(json.dumps(out))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/p54_probe_gathered.json", "w") as f:
            json.dump(out, f)
        return

    q, k, v, qi, ki, w, co = inputs(jax.random.key(4), B, S, H, KVH, D, HI,
                                    dI, jnp.bfloat16)
    qt, kt, vt, cot = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, co))
    qi_t, ki_t = jnp.swapaxes(qi, 1, 2), jnp.swapaxes(ki, 1, 2)
    w_t = jnp.swapaxes(w, 1, 2)[:, :, None]
    ms, (bits, lse_i, cnt) = timed(jax.jit(
        lambda a, b, c: sa.select(a, b, c, topk)), qi_t, ki_t, w_t)
    out["select_ms"] = ms
    want = jnp.minimum(jnp.arange(S) + 1, topk)
    out["count_exact"] = bool((cnt[0, 0, 0] == want).all())
    ms, (o, lse) = timed(jax.jit(
        lambda *a: sa._attend_fwd(*a, scale)), qt, kt, vt, bits)
    out["fwd_ms"] = ms
    ms, _ = timed(jax.jit(lambda *a: sa._attend_bwd(*a, scale)),
                  qt, kt, vt, bits, o, lse, cot)
    out["bwd_ms"] = ms
    # the core's tiles (bq, bk; bk divides a plane of S / 32 keys)
    out["tiles_fwd_bwd_ms"] = {}
    for tiles in ((512, 512), (1024, 512), (1024, 1024), (2048, 512),
                  (2048, 1024), (512, 1024)):
        try:
            f, (o2, lse2) = timed(jax.jit(lambda *a, t=tiles: sa._attend_fwd(
                *a, scale, t)), qt, kt, vt, bits, n=3)
            b, _ = timed(jax.jit(lambda *a, t=tiles: sa._attend_bwd(
                *a, scale, t)), qt, kt, vt, bits, o2, lse2, cot, n=3)
            same = float(jnp.abs(o2.astype(jnp.float32)
                                 - o.astype(jnp.float32)).max())
            out["tiles_fwd_bwd_ms"]["%dx%d" % tiles] = [f, b, same]
        except Exception as e:  # a tile the compiler refuses
            out["tiles_fwd_bwd_ms"]["%dx%d" % tiles] = str(e)[:200]
    ms, _ = timed(jax.jit(lambda *a: sa._index_loss_call(*a, scale)),
                  qt, kt, lse, qi_t, ki_t, w_t, bits, lse_i)
    out["index_loss_ms"] = ms
    pairs = float(want.sum())
    out["selected_pct"] = 100.0 * pairs / (S * (S + 1) / 2)
    print(json.dumps(out))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/p54_probe_dsa.json", "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
