"""Held-experts probe on the chip: one expert layer alone (`ops/moe.py`
`moe_ffn_held` under the model's `"dots"` remat policy), forward + backward,
at the shapes of the two cells that run it, at the module's own window and
for each rule of `RULES` (a factor on the even share, alone): ms a call, the window's rows, the trips the loop took and the rows it
worked. `HELD_WINDOW_FACTOR` of ops/moe.py is filled from these lines (the
sweep beside the constant).

The routing is what a random router gives random tokens: close to even
(`assigned` over `even` in each line), so a margin of 1.125 and more takes
one trip and the half- and quarter-share windows more. `skew` adds a
selection bias towards the held range (`sigmoid_route`'s, in both cells:
only that route has one), for the cost of what falls past the module's first
window at each share of it that a further window may have (`SHARES`).

`parts` times the layer's passes one by one at a window of W rows: the row
gather, the scatter-add back (as it is, and with the rows sorted by token
first), the combine written as a gather over every token's k positions, and
the gate/up grouped product with its two transposes through both paths of
`moe.grouped_products` (`lax.ragged_dot`; the Pallas grouped matmul at
`moe._tiles`) and, at the module's window, at each tiling of `GMM_TILES`
(the sweep beside `_tiles`).

`rows` times the layer, forward + backward, as the held total grows: the
routing GIVEN (`RATIOS` of the held experts' even share put on the held range,
the rest elsewhere; the weights still the router's, so every gradient is
made), at the module's window, for each number of blocks a window's row
passes may be cut into (`BLOCKS`; `moe.block_rows`): ms a call, the rows the
passes worked, and a checksum of the loss's and every gradient's bits. Run
in a tree without `moe.block_rows` (a parent's, with this file copied into
it) it gives that tree's body at the same inputs: the line to set beside.

`combine` times a first window's sum back to the tokens (the combine, and
`dx` in the backward) at each routed cell's (tokens, d, window rows, held
rows), both ways: the scatter-add (`moe.scatter_rows`) against the token order
(`moe.token_order`; and the same order from a prefix sum and two integer
scatters), the row pass through it (the forward's scaling pass, which cuts
its blocks today; the backward's, a pass more) and the one-hot + grouped
product (`moe.block_sums`) at each of `TOKEN_BLOCKS` and `SUM_TILES`, each
part alone and the whole, with the largest difference between the two sums
(NaN is written past the held rows: none may reach a sum).

One JSON line a case. Off the chip the script fails at once.

    python3 benchmarks/probe_moe.py            # the sweep, both cells
    python3 benchmarks/probe_moe.py skew       # the module's window, skewed
    python3 benchmarks/probe_moe.py parts      # the passes alone
    python3 benchmarks/probe_moe.py rows       # ms against the held total
    python3 benchmarks/probe_moe.py combine    # the window's sum, both ways
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import moe
from ray_tpu.util.jaxenv import enable_compile_cache, require_tpu

# (name, tokens, d, experts, held first, held count, expert width, k, route):
# mellum2_12b_a2_5b.train_share_16k and kimi_linear_48b_a3b.train_share_8k.
CELLS = [("mellum2", 16384, 2304, 64, 16, 16, 896, 8, "softmax"),
         ("kimi_linear", 8192, 2304, 256, 104, 8, 1024, 8, "sigmoid")]
# `rows` adds kanana_2_30b_a3b.train_rank8_16k (the other modes' sweeps are
# recorded beside the module's constants for the two above).
KANANA = ("kanana", 16384, 2048, 128, 48, 16, 768, 6, "sigmoid")
RATIOS = (0.5, 1.0, 1.5, 2.0, 2.5)  # held total over the even share, `rows`
BLOCKS = (5, 10, 20, 40)            # blocks a window, `rows`
# Window rules: the factor on the held experts' even share. 4.0 is what PRs
# 27-33 ran (with a quarter of the experts held: every assignment).
RULES = (4.0, 1.5, 1.25, 1.125, 0.5, 0.25)
SKEW = (0.0, 0.03, 0.06, 0.12)  # added to the held experts' scores in the selection
# (rows, contracted, columns) tiles of the Pallas grouped matmul, for `parts`.
GMM_TILES = ((512, 768, 896), (512, 1152, 896), (1024, 768, 896),
             (256, 1152, 896), (512, 1152, 1792), (512, 2304, 896))
# `combine`: (cell, tokens, d, first window's rows, held rows in it).
SUMS = (("mellum2", 16384, 2304, 81920, 37000),
        ("mellum2", 16384, 2304, 81920, 60000),
        ("kanana", 16384, 2048, 30720, 12000),
        ("qwen3_next", 16384, 2048, 25600, 10000),
        ("laguna", 8192, 3072, 8192, 3000),
        ("kimi_linear", 8192, 2304, 8192, 4500))
K = 8  # assignments a token (what the held rows' tokens are drawn from)
TOKEN_BLOCKS = (128, 256, 512)
# (rows, columns) tiles of the sum's product beside `moe._tiles`' own (None:
# the whole of the case, the others the product alone); a block of tokens is
# one tile.
SUM_TILES = (None, (256, 1152), (1024, 1152), (512, 768), (512, 2304),
             (256, 1024), (1024, 1024), (512, 2048), (512, 1536))
FACTOR = moe.HELD_WINDOW_FACTOR  # the module's own, which the rules replace
MIN_TOKENS = getattr(moe, "HELD_WINDOW_MIN_TOKENS", 0.0)  # (a rule: factor alone)
SHARE = getattr(moe, "FURTHER_WINDOW_SHARE", 1.0)
SHARES = (1.0, 0.5, 0.25, 0.125)  # of the first window, a further one
BLOCK_ROWS = getattr(moe, "block_rows", None)  # (a parent's tree has none)


def _case(T, d, E, first, Eh, F, k, kind, skew=0.0):
    ks = jax.random.split(jax.random.key(T + E), 5)
    if kind == "sigmoid" or skew:  # only this route has a selection bias
        route = functools.partial(
            moe.sigmoid_route, experts_per_token=k, routed_scale=2.446,
            bias=jnp.zeros((E,)).at[first:first + Eh].add(skew))
    else:
        route = functools.partial(moe.softmax_route, experts_per_token=k)
    x = jax.random.normal(ks[0], (1, T, d), jnp.bfloat16)
    params = (jax.random.normal(ks[1], (d, E)) * d ** -0.5,
              jax.random.normal(ks[2], (Eh, d, 2, F)) * 0.02,
              jax.random.normal(ks[3], (Eh, F, d)) * 0.02)
    wy = jax.random.normal(ks[4], (1, T, d), jnp.bfloat16)
    return x, params, wy, route


def _layer(route, first):
    """loss and gradients (x, router, both weight stacks) of the layer as a
    stack's layer body runs it: under `jax.checkpoint` with what `"dots"`
    keeps."""
    policy = jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(
            *getattr(moe, "RESIDUAL_NAMES", ())))

    @functools.partial(jax.checkpoint, policy=policy)
    def body(x, rw, wgu, wd):
        return moe.moe_ffn_held(x, rw, wgu, wd, route=route,
                                held_first=first)

    def loss(x, rw, wgu, wd, wy):
        y, cnt = body(x, rw, wgu, wd)
        return jnp.sum(y.astype(jnp.float32) * wy), cnt

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                      has_aux=True))


def _ms(fn, args, steps=10, repeats=3):
    """ms a call: the least of `repeats` means over `steps` calls in flight."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return round(best, 3)


def layer_ms(cell, factor, skew=0.0, share=None):
    name, T, d, E, first, Eh, F, k, kind = cell
    moe.HELD_WINDOW_FACTOR = factor or FACTOR  # read when the layer is traced
    moe.HELD_WINDOW_MIN_TOKENS = 0.0 if factor else MIN_TOKENS
    moe.FURTHER_WINDOW_SHARE = share or SHARE
    x, params, wy, route = _case(T, d, E, first, Eh, F, k, kind, skew)
    fn = _layer(route, first)
    t0 = time.perf_counter()
    (_, cnt), grads = jax.block_until_ready(fn(x, *params, wy))
    row = {"cell": name, "factor": moe.HELD_WINDOW_FACTOR,
           "min_tokens": moe.HELD_WINDOW_MIN_TOKENS, "skew": skew,
           "further_share": moe.FURTHER_WINDOW_SHARE,
           "compile_s": round(time.perf_counter() - t0, 2),
           "window_rows": moe.held_window_rows(T, k, E, Eh),
           "even": T * k * Eh // E, "assigned": float(cnt["assigned"]),
           "dropped": float(cnt["dropped"]),
           "finite": all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
                         for g in grads)}
    if "trips" in cnt:
        row["trips"] = float(cnt["trips"])
        row["rows_worked"] = float(cnt["rows_worked"]) if (  # since PR 43
            "rows_worked" in cnt) else row["window_rows"] + (
                row["trips"] - 1) * moe.further_window_rows(
                    row["window_rows"])
    row["ms"] = _ms(fn, (x, *params, wy))
    return row, row["dropped"] == 0.0 and row["finite"]


def _given_ids(T, k, E, first, Eh, held, seed=0):
    """[T, k] expert ids with `held` assignments on the held range, spread
    evenly over its experts, and the rest evenly over the others."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, E - Eh, T * k)
    ids = np.where(ids >= first, ids + Eh, ids)
    ids[rng.choice(T * k, held, replace=False)] = first + rng.integers(
        0, Eh, held)
    return jnp.asarray(ids.reshape(T, k), jnp.int32)


def rows_ms(cell, blocks):
    """The layer at the module's window, the routing given, at each of
    `RATIOS`; `blocks` None: the tree's own body (`moe.block_rows` as it is,
    or a tree that has none)."""
    name, T, d, E, first, Eh, F, k, kind = cell
    moe.HELD_WINDOW_FACTOR, moe.HELD_WINDOW_MIN_TOKENS = FACTOR, MIN_TOKENS
    moe.FURTHER_WINDOW_SHARE = SHARE
    if blocks:
        moe.block_rows = functools.partial(BLOCK_ROWS, blocks=blocks)
    x, params, wy, _ = _case(T, d, E, first, Eh, F, k, kind)

    def given(ids, xf, rw):  # the routing given, the weights the router's
        w = jnp.take_along_axis(jax.nn.sigmoid(
            xf.astype(jnp.float32) @ rw.astype(jnp.float32)), ids, axis=-1)
        return ids, w / jnp.sum(w, axis=-1, keepdims=True)

    @jax.jit
    def fn(ids, x, rw, wgu, wd, wy):
        (loss, cnt), grads = _layer(functools.partial(given, ids), first)(
            x, rw, wgu, wd, wy)
        bits = lambda a: jnp.sum(jax.lax.bitcast_convert_type(
            a, {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]).astype(
                jnp.uint32))
        finite = jnp.stack([jnp.isfinite(g.astype(jnp.float32)).all()
                            for g in grads]).all()
        return cnt, finite, jnp.stack([bits(g) for g in (loss,) + grads])

    W = moe.held_window_rows(T, k, E, Eh)
    even = T * k * Eh // E
    row = {"cell": name, "window_rows": W, "even": even, "blocks": blocks,
           "block_rows": BLOCK_ROWS and moe.block_rows(W), "cases": []}
    ok = True
    for i, ratio in enumerate(RATIOS):
        args = (_given_ids(T, k, E, first, Eh, int(ratio * even)), x,
                *params, wy)
        t0 = time.perf_counter()
        cnt, finite, sums = jax.block_until_ready(fn(*args))
        if not i:
            row["compile_s"] = round(time.perf_counter() - t0, 2)
        case = {"ratio": ratio, "assigned": float(cnt["assigned"]),
                "dropped": float(cnt["dropped"]), "finite": bool(finite),
                "trips": float(cnt["trips"]),
                "rows_worked": float(cnt.get("rows_worked", W)),
                "ms": _ms(fn, args),
                "sums": [int(v) for v in sums]}
        ok = ok and case["finite"] and case["dropped"] == 0.0
        row["cases"].append(case)
    return row, ok


def parts_ms(cell, factor):
    """The passes of one window alone, at the rule's rows, groups even."""
    name, T, d, E, first, Eh, F, k, kind = cell
    moe.HELD_WINDOW_FACTOR, moe.HELD_WINDOW_MIN_TOKENS = factor, 0.0
    W = moe.held_window_rows(T, k, E, Eh)
    ks = jax.random.split(jax.random.key(0), 6)
    bf = jnp.bfloat16
    xf = jax.random.normal(ks[0], (T, d), bf)
    yb = jax.random.normal(ks[1], (W, d), bf)
    gu = jax.random.normal(ks[2], (W, 2 * F), bf)
    w1 = jax.random.normal(ks[3], (Eh, d, 2 * F), bf)
    tok = jax.random.randint(ks[4], (W,), 0, T)
    pos = jax.random.randint(ks[5], (T, k), 0, 4 * W)  # a quarter in reach
    sizes = jnp.full((Eh,), W // Eh, jnp.int32)
    row = {"cell": name, "factor": factor, "window_rows": W}

    def sorted_scatter(yb, tok):
        by_token = jnp.argsort(tok)
        return jnp.zeros((T, d), bf).at[tok[by_token]].add(
            yb[by_token], mode="drop", indices_are_sorted=True)

    cases = {
        "gather_ms": (lambda xf, tok: xf.at[tok].get(
            mode="fill", fill_value=0), (xf, tok)),
        "scatter_add_ms": (lambda yb, tok: jnp.zeros((T, d), bf).at[tok].add(
            yb, mode="drop"), (yb, tok)),
        "scatter_add_sorted_ms": (sorted_scatter, (yb, tok)),
        "combine_as_gather_ms": (lambda yb, pos: jnp.sum(yb.at[pos].get(
            mode="fill", fill_value=0), axis=1), (yb, pos)),
    }
    for key, (fn, args) in cases.items():
        row[key] = _ms(jax.jit(fn), args, steps=20)

    def products(label):
        """[W, d] x [Eh, d, 2F] and its two transposes, as the layer's
        gate/up product makes them."""
        for key, fn, args in zip(
                ("product", "product_t_rows", "product_t_weights"),
                moe.grouped_products(label != "ragged_dot", bf),
                ((yb, w1, sizes), (gu, w1, sizes), (yb, gu, sizes))):
            try:
                row[f"{key}_{label}_ms"] = _ms(jax.jit(fn), args, steps=20)
            except Exception as e:  # a tiling the kernel refuses
                row[f"{key}_{label}_error"] = f"{type(e).__name__}: {e}"[:160]

    products("ragged_dot")
    products("kernel")
    if factor == FACTOR:
        tiles_fn = moe._tiles
        try:
            for tiles in GMM_TILES:
                moe._tiles = lambda m, k, n, tiles=tiles: tiles
                products("kernel_%dx%dx%d" % tiles)
        finally:
            moe._tiles = tiles_fn
    return row, True


def combine_ms(case):
    """One window's sum back to the tokens, by the scatter-add and by the
    token-ordered product, part by part."""
    name, T, d, W, held = case
    bf = jnp.bfloat16
    rng = np.random.default_rng(W + held)
    # A token's k assignments fall on k experts: in the window's order (by
    # expert) the tokens of the held rows come as drawn.
    at_w = np.full(W, T * K, np.int32)
    at_w[:held] = rng.choice(T * K, held, replace=False)
    at_w = jnp.asarray(at_w)
    tok = at_w // K
    vals = jnp.where((jnp.arange(W) < held)[:, None], jax.random.normal(
        jax.random.key(held), (W, d), bf), jnp.nan)
    wrow = jax.random.uniform(jax.random.key(1), (W,), jnp.float32)
    n = jnp.int32(held)
    block = moe.block_rows(W)
    row = {"cell": name, "tokens": T, "d": d, "window_rows": W, "held": held,
           "block_rows": block}

    def passes(body, init, n):
        return jax.lax.fori_loop(0, (n + block - 1) // block, lambda b, c: (
            jax.lax.dynamic_update_slice_in_dim(c, body(b * block), b * block,
                                                0)), init)

    cut = lambda a, at: jax.lax.dynamic_slice_in_dim(a, at, block)
    f32 = lambda a: a.astype(jnp.float32)
    scale_cut = lambda vals, wrow, n: passes(lambda at: (
        f32(cut(vals, at)) * cut(wrow, at)[:, None]).astype(bf),
        jax.lax.empty((W, d), bf), n)
    scale_by = lambda vals, wrow, by, n: passes(lambda at: (
        f32(vals[cut(by, at)]) * wrow[cut(by, at)][:, None]).astype(bf),
        jax.lax.empty((W, d), bf), n)
    through = lambda vals, by, n: passes(
        lambda at: vals[cut(by, at)], jax.lax.empty((W, d), bf), n)

    def order_scans(at_w):
        """`moe.token_order`'s permutation with no sort, from the rows'
        assignments `at_w` (token x k + choice; T k where a row holds none):
        a row's place is the number of the window's assignments below its
        own, a prefix sum over a mask of them (an integer scatter), and the
        permutation is the places' inverse (a second)."""
        here = jnp.zeros((T * K + 1,), jnp.int32).at[at_w].set(1)
        place = jnp.where(at_w < T * K, (jnp.cumsum(here) - here)[at_w],
                          jnp.arange(W))
        return jnp.zeros((W,), jnp.int32).at[place].set(
            jnp.arange(W, dtype=jnp.int32))

    order = jax.jit(lambda tok: moe.token_order(tok, T, 256))
    by, tok_t, _ = order(tok)
    # (Inside a token the sort keeps the window's order, by expert, and the
    # prefix sum the choices': the same runs of rows.)
    row["orders_agree"] = bool(jnp.all(
        tok[jax.jit(order_scans)(at_w)] == tok_t))
    parts = {
        "scatter_ms": (lambda tok, vals, n: moe.scatter_rows(
            tok, vals, n, T), (tok, vals, n)),
        "order_sort_ms": (order, (tok,)),
        "order_scans_ms": (order_scans, (at_w,)),
        "scale_cut_ms": (scale_cut, (vals, wrow, n)),
        "scale_through_ms": (scale_by, (vals, wrow, by, n)),
        "pass_through_ms": (through, (vals, by, n)),
        "today_fwd_ms": (lambda tok, vals, wrow, n: moe.scatter_rows(
            tok, scale_cut(vals, wrow, n), n, T), (tok, vals, wrow, n)),
        "today_bwd_ms": (lambda tok, vals, n: moe.scatter_rows(
            tok, vals, n, T), (tok, vals, n)),
    }
    for key, (fn, args) in parts.items():
        row[key] = _ms(jax.jit(fn), args, steps=20)
    want = f32(jax.jit(parts["today_fwd_ms"][0])(tok, vals, wrow, n))
    ok = True
    for tb in TOKEN_BLOCKS:
        sizes = jax.jit(lambda tok: moe.token_order(tok, T, tb)[2])(tok)
        for tiles in SUM_TILES:
            if tiles and d % tiles[1]:
                continue
            label = "%d_%s" % (tb, "%dx%d" % tiles if tiles else "own")
            tiling = tiles and (lambda m, k, n, tiles=tiles: (
                tiles[0], tb, tiles[1]))
            try:
                row[f"sum_{label}_ms"] = _ms(jax.jit(
                    lambda tok_t, rows_t, sizes: moe.block_sums(
                        tok_t, rows_t, sizes, T, tb, tiling)),
                    (tok_t, vals[by], sizes), steps=20)
            except Exception as e:  # a tiling the kernel refuses
                row[f"error_{label}"] = f"{type(e).__name__}: {e}"[:160]

        def fwd(tok, vals, wrow, n):
            by, tok_t, sizes = moe.token_order(tok, T, tb)
            return moe.block_sums(tok_t, scale_by(vals, wrow, by, n), sizes,
                                  T, tb)

        def bwd(tok, vals, n):
            by, tok_t, sizes = moe.token_order(tok, T, tb)
            return moe.block_sums(tok_t, through(vals, by, n), sizes, T, tb)

        row[f"fwd_{tb}_ms"] = _ms(jax.jit(fwd), (tok, vals, wrow, n),
                                  steps=20)
        row[f"bwd_{tb}_ms"] = _ms(jax.jit(bwd), (tok, vals, n), steps=20)
        got = f32(jax.jit(fwd)(tok, vals, wrow, n))
        row[f"diff_{tb}"] = float(jnp.max(jnp.abs(got - want)))
        ok = ok and row[f"diff_{tb}"] <= 0.0625 * float(
            jnp.max(jnp.abs(want)))
    return row, ok


def main(argv) -> int:
    dev = require_tpu()
    enable_compile_cache()
    print(json.dumps({"device_kind": dev.device_kind,
                      "devices": len(jax.devices())}), flush=True)
    failed = 0

    def report(fn, *args):
        nonlocal failed
        try:
            row, ok = fn(*args)
        except Exception as e:  # report every case, fail at the end
            row, ok = {"args": repr(args)[:200],
                       "error": f"{type(e).__name__}: {e}"[:600]}, False
        row["ok"] = ok
        failed += not ok
        print(json.dumps(row), flush=True)

    if argv[1:] == ["combine"]:
        for case in SUMS:
            report(combine_ms, case)
        return 1 if failed else 0
    for cell in CELLS + [KANANA] if argv[1:] == ["rows"] else CELLS:
        if argv[1:] == ["rows"]:
            seen = set()
            W = moe.held_window_rows(cell[1], cell[7], cell[3], cell[5])
            for blocks in BLOCKS if BLOCK_ROWS else (None,):
                rows = blocks and BLOCK_ROWS(W, blocks)
                if rows not in seen:  # (the hybrid's 5 and 10 are one block)
                    seen.add(rows)
                    report(rows_ms, cell, blocks)
        elif argv[1:] == ["skew"]:
            for skew in SKEW:
                for share in SHARES if skew else SHARES[:1]:
                    report(layer_ms, cell, None, skew, share)
        elif argv[1:] == ["parts"]:
            for factor in RULES[:4]:
                report(parts_ms, cell, factor)
        else:
            for factor in (None,) + RULES:  # the module's own rule first
                report(layer_ms, cell, factor)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
