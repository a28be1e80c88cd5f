#!/usr/bin/env python3
"""Chip probe of ops/sparse_attention.py at the Keye-VL-2.0 cell's shape
([1, S, 32|4, 128], an indexer of 16 x 64, top 2,048): each kernel's ms a
call, the core's two kernels over a few tiles, and the selection's count to
the unit, the passes its search ran and the ways its blocks went, held bit
for bit to the parent's kernel (PR 54's fixed 33 + 15 passes a block, kept
below as a copy) with that kernel's time at all, half and none of its trips
(the price of a pass; `select` as a second argument stops there); and, with
`gathered` as a second argument, ISSUE 54's other way
alone: attention over kept keys brought by index through XLA's gather, on
2,048 late query rows (a sixteenth of the layer). (What the kernels compute is held to the plain reference by the
cell's own comparison, chipbench/drivers/train_stack_sparse.py, at the cell's
size; tests/test_dsa.py at a small one.) Refuses to run off the chip.

    chiprun -- python3 benchmarks/probe_dsa.py [S] [gathered | select | core]

`core` as a second argument: the selection once (for its bits), then the
core's two kernels alone at `plan`'s tiles, at 512 x 512 (four times the grid
steps over the same triangle) and at 2,048 x 1,024 (not square: the
rectangular grid), each beside a head's grid steps and the idle ones among
them, and the first results' sha256: PR 57 ran it in its parent's tree and
in its own for the price of an idle grid step and of a working one.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import dispatch
from ray_tpu.ops import flash_attention as fa
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


# ------------------------------------------------- the parent's selection
#
# ops/sparse_attention.py's `_select_kernel` as PR 54 shipped it, kept here
# (not in ray_tpu/) as what the adaptive search is held to bit for bit, and
# for the price of a counting pass: `trips` scales the three search loops'
# trip counts (1.0 the parent's 32 + 1 + nbits passes; 0.5 half of them, 0.0
# none: the answers are then wrong and only the time is read).


def _parent_select_kernel(qi_ref, ki_ref, w_ref, bits_ref, lse_ref, cnt_ref,
                          keys_scr, w_scr, *, S, topk, R, ck, planes, heads,
                          trips):
    _iota, _flip, _F32 = sa._iota, sa._flip, jnp.float32
    ib = pl.program_id(1)
    for j in range(heads):
        w_scr[j] = w_ref[j].T
    row = ib * R + _iota((R, 1), 0)
    n_ck = ((ib + 1) * R + ck - 1) // ck
    fold = ck // 128 if ck % 128 == 0 else 1

    def cols(c):
        return pl.ds(pl.multiple_of(c * ck, ck), ck)

    def fill(c, _):
        scores = sa._index_scores(qi_ref, w_scr, ki_ref[:, cols(c)], heads)
        key = _flip(jax.lax.bitcast_convert_type(scores, jnp.int32))
        col = c * ck + _iota((R, ck), 1)
        keys_scr[:, cols(c)] = jnp.where(col <= row, key, sa._INT_MIN)
        return 0

    jax.lax.fori_loop(0, n_ck, fill, 0)

    def count(pred):
        def body(c, acc):
            hit = pred(keys_scr[:, cols(c)], c * ck).astype(_F32)
            if fold == 1:
                return acc + jnp.sum(hit, axis=1, keepdims=True)
            for f in range(fold):
                acc = acc + hit[:, f * 128:(f + 1) * 128]
            return acc
        acc = jax.lax.fori_loop(
            0, n_ck, body, jnp.zeros((R, 1 if fold == 1 else 128), _F32))
        return acc if fold == 1 else jnp.sum(acc, axis=1, keepdims=True)

    k_eff = jnp.minimum(row + 1, topk).astype(_F32)

    def value_bit(i, u):
        cand = u | jnp.left_shift(jnp.int32(1), 31 - i)
        n = count(lambda key, _: key >= (cand ^ sa._INT_MIN))
        return jnp.where(n >= k_eff, cand, u)

    tau = jax.lax.fori_loop(0, int(32 * trips), value_bit,
                            jnp.zeros((R, 1), jnp.int32)) ^ sa._INT_MIN
    if trips > 0:
        ties = k_eff - count(lambda key, _: key > tau)
    else:
        ties = k_eff
    nbits = max(1, (S - 1).bit_length())

    def index_bit(i, x):
        cand = x | jnp.left_shift(jnp.int32(1), nbits - 1 - i)
        n = count(lambda key, c0: (key == tau)
                  & (c0 + _iota((R, ck), 1) < cand))
        return jnp.where(n <= ties - 1.0, cand, x)

    last = jax.lax.fori_loop(0, int(nbits * trips), index_bit,
                             jnp.zeros((R, 1), jnp.int32))

    word = jnp.zeros((R, planes), jnp.int32)
    cnt = jnp.zeros((R, 1), _F32)
    m = jnp.full((R, 1), sa._NEG_INF, _F32)
    l = jnp.zeros((R, 1), _F32)
    for p in range(32):
        key = keys_scr[:, p * planes:(p + 1) * planes]
        col = p * planes + _iota((R, planes), 1)
        sel = ((key > tau) | ((key == tau) & (col <= last))) & (col <= row)
        word = word | jnp.left_shift(sel.astype(jnp.int32), p)
        cnt = cnt + jnp.sum(sel.astype(_F32), axis=1, keepdims=True)
        scores = jnp.where(sel, jax.lax.bitcast_convert_type(
            _flip(key), _F32), sa._NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.where(sel, jnp.exp(scores - m_new), 0.0), axis=1,
            keepdims=True)
        m = m_new
    bits_ref[...] = word
    lse_ref[...] = (m + jnp.log(l)).T
    cnt_ref[...] = cnt.T


def parent_select(qi, ki_t, w, topk, trips=1.0):
    """`sa.select` as the parent of PR 55 had it -> bits, lse_i, count."""
    B, HI, S, dI = qi.shape
    pn = sa.plan(S)
    R, ck = pn.rows, pn.chunk
    stat = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.float32)
    return pl.pallas_call(
        functools.partial(_parent_select_kernel, S=S, topk=topk, R=R, ck=ck,
                          planes=pn.planes, heads=HI, trips=trips),
        grid=(B, S // R),
        in_specs=[
            pl.BlockSpec((None, HI, R, dI), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((None, dI, S), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, HI, 1, R), lambda b, i: (b, 0, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((None, R, pn.planes), lambda b, i: (b, i, 0)),
            sa._stat_spec(R, lambda b, i: (b, 0, 0, i)),
            sa._stat_spec(R, lambda b, i: (b, 0, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, S, pn.planes), jnp.int32),
                   stat, stat],
        scratch_shapes=[pltpu.VMEM((R, S), jnp.int32),
                        pltpu.VMEM((HI, R, 1), jnp.float32)],
        compiler_params=sa._params("parallel", "arbitrary"),
        name="dsa_select_parent", interpret=dispatch.interpret(),
    )(qi, ki_t, w)


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
        with open("chiprun_out/probe_dsa_gathered.json", "w") as f:
            json.dump(out, f)
        return

    q, k, v, qi, ki, w, co = inputs(jax.random.key(4), B, S, H, KVH, D, HI,
                                    dI, jnp.bfloat16)
    qt, kt, vt, cot = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, co))
    qi_t, ki_t = jnp.swapaxes(qi, 1, 2), jnp.swapaxes(ki, 1, 2)
    w_t = jnp.swapaxes(w, 1, 2)[:, :, None]
    ms, (bits, lse_i, cnt, passes, way) = timed(jax.jit(
        lambda a, b, c: sa.select(a, b, c, topk)), qi_t, ki_t, w_t)
    out["select_ms"] = ms
    if sys.argv[2:] == ["core"]:
        pn = sa.plan(S)
        out["core"] = {}
        for tiles in ((pn.bq, pn.bk), (512, 512), (2048, 1024),
                      (pn.bq, pn.bk)):
            f, (o, lse) = timed(jax.jit(lambda *a, t=tiles: sa._attend_fwd(
                *a, scale, t)), qt, kt, vt, bits, n=10)
            b, grads = timed(jax.jit(lambda *a, t=tiles: sa._attend_bwd(
                *a, scale, t)), qt, kt, vt, bits, o, lse, cot, n=10)
            row = {"fwd_ms": f, "bwd_ms": b,
                   "grid_steps": fa.grid_steps(S, *tiles, True)}
            if not out["core"]:  # the results' bits, to hold against a tree's
                row["sha256"] = {name: hashlib.sha256(np.asarray(
                    x.astype(jnp.float32)).tobytes()).hexdigest()[:16]
                    for name, x in zip(("o", "lse", "dq", "dk", "dv"),
                                       (o, lse) + tuple(grads))}
            out["core"].setdefault("%dx%d" % tiles, []).append(row)
        print(json.dumps(out))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/probe_dsa_core.json", "w") as f:
            json.dump(out, f)
        return
    # the search: counting passes a block of rows (the parent ran 33 + the
    # index's bits in every block), and the blocks by the way they went
    out["select_passes"] = float(passes.mean()) / sa._SAMPLE
    out["select_passes_max"] = float(passes.max()) / sa._SAMPLE
    out["select_ways"] = [int((way == i).sum()) for i in range(3)]
    want = jnp.minimum(jnp.arange(S) + 1, topk)
    out["count_exact"] = bool((cnt[0, 0, 0] == want).all())
    # the parent's kernel on the same inputs: bit for bit, and the price of
    # its passes from the same kernel at half and at none of its trips
    for trips in (1.0, 0.5, 0.0):
        ms, old = timed(jax.jit(lambda a, b, c, t=trips: parent_select(
            a, b, c, topk, t)), qi_t, ki_t, w_t)
        out["parent_select_ms_trips_%g" % trips] = ms
        if trips == 1.0:
            out["parent_bit_for_bit"] = {
                name: bool((x == y).all()) for name, x, y in zip(
                    ("bits", "lse_i", "count"), (bits, lse_i, cnt), old)}
    # the tie search on the chip: every key met twice (most blocks hold a
    # row that keeps one of two) and scores rounded to a few values (rows
    # keep many of their ties), each against the parent's kernel
    both = jax.jit(lambda a, b, c: (sa.select(a, b, c, topk),
                                    parent_select(a, b, c, topk)))
    for name, (a, b, c) in (
            ("met_twice", (qi_t, jnp.repeat(ki_t[:, :, ::2], 2, axis=2), w_t)),
            ("rounded", (jnp.round(qi_t), jnp.round(ki_t),
                         jnp.round(w_t * 64) / 8))):
        new, old = both(a, b, c)
        out["ties_" + name] = {
            "bit_for_bit": all(bool((x == y).all())
                               for x, y in zip(new[:3], old)),
            "passes": float(new[3].mean()) / sa._SAMPLE,
            "ways": [int((new[4] == i).sum()) for i in range(3)]}
    if sys.argv[2:] == ["select"]:
        print(json.dumps(out))
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/probe_dsa_select.json", "w") as f:
            json.dump(out, f)
        return
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
    with open("chiprun_out/probe_dsa.json", "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
