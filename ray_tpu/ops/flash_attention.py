"""Pallas TPU flash attention (forward + backward), GQA-aware.

The reference framework ships no attention kernels (it delegates the model
math to torch; SURVEY.md §5.7 — long-context is a first-class gap to fill).
Here the flash kernel is the MFU-critical op: online-softmax tiling keeps the
S×S logits out of HBM, and the backward pass recomputes P from saved per-row
logsumexp instead of storing probabilities.

Layout: the public entry takes [B, S, H, D] (model layout) and transposes to
[B, H, S, D] so the trailing two block dims are (block_s, head_dim) — full
(sublane, lane) tiles. XLA fuses the transposes into neighbouring ops. The row
statistics (`lse`, `delta`) are [B, H, S] float32 and cross the kernels as
(1, block) blocks, the sequence on the lanes.

Grid convention: the innermost grid dimension is the contraction over KV (or
Q, in the backward kernel) blocks; TPU grids execute sequentially so VMEM
scratch accumulators carry across it ("arbitrary" dimension semantics), and
outputs are flushed on the last step of a row (a q block's key blocks, or a
key block's q blocks: `_grid_place` gives the step within the row and the
row's steps). A rectangular grid has a row an outer index and every block of
the other side, or those a window's band reaches, inside it. A full causal
call whose blocks are square and divide the sequence into an even number n of
them takes the FOLDED grid (`_folded`, `_fold`; from the static shapes, no
setting): row p of the triangle and row n - 1 - p are one line of n + 1
steps, n / 2 lines, every step a tile at or below the diagonal, where the
rectangle entered n (n - 1) / 2 of its n x n steps to do nothing. The output
block's index then moves once inside the inner dimension and is never
revisited; within a row the blocks still arrive in ascending order, so o,
lse, dK and dV are the rectangle's bit for bit (the fused backward's dQ sums
a row's key blocks in the order the grid brings them: float32 rounding).

The backward is one kernel (`_dkv_kernel` with dQ's output and accumulator;
`bwd_kind` says "fused"): for a (batch, head) it walks every key block and
the query blocks that see it, computes S^T, dP^T and the exponential once a
cell and takes dV, dK and dQ from them, five products. dQ = dS^T (transposed
on the left) K is added into a float32 accumulator that holds the whole head
in VMEM across the key blocks, zeroed at the head's first grid step and
written out once at its last. A head whose accumulator and output block pass
`FUSED_DQ_VMEM_BUDGET` takes the two kernels the fused one replaced (`_dq_kernel`
beside `_dkv_kernel` without dQ: "split"; S and dP are then computed twice).

A tile's work, in all the kernels alike (`_tile_class`, `tile_plan`): a grid
tile wholly above the diagonal is *skipped* (the folded grid has no step for
it; the rectangle enters the step, computes nothing, and its index maps stay
on the last needed block, so nothing is fetched: `grid_steps` counts both,
and `flash.plan` says them); one wholly
below it and inside the sequence is *interior* and runs with no mask at all;
only an *edge* tile (cut by the diagonal or by a ragged last block) builds
one. Inside a grid step the accumulating side goes in strips of `sub`
positions, and on a diagonal tile each strip meets only the other side's
positions on its side of the diagonal: `sub` x `sub` cells on the diagonal
under a fixed triangle, the rest unmasked, the cells above it never computed.

A window (`window=W`: query i sees keys j with 0 <= i - j < W) is a second
boundary below the diagonal. Tiles wholly below the band are skipped like
those above the diagonal, and the grid's inner dimension is no longer every
block of the other side but the few the band of one block can reach
(`_band_steps`: two where W <= block), the index maps starting at the band's
first block: a layer's grid steps, fetches and work follow S x W. A tile the
lower boundary cuts is an edge tile too and is taken by the same cells, each
skipped, unmasked or masked from its static place (`_cells`).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

# The fallback for shapes `_TILES` does not know, and the grid block of a
# sequence the table's block does not divide.
DEFAULT_BLOCK = 512
# (D, Dv) of a 2-byte dtype -> (grid block, strip), from the sweep of
# benchmarks/probe_flash.py on a v5e at the three training cells' shapes
# (chip run PR 30, `sweep2`; ms a call forward + dQ + dK/dV, causal; the
# parent's 512 x 512 with the mask on every tile first):
#   [16,1024,12,64]        6.07 | 512/256 4.37 | 1024/128 3.49 | 1024/256 3.74
#   [4,2048,8|4,128]       2.08 | 1024/256 1.39 | 2048/256 1.19 | 2048/512 1.27
#   [1,8192,32,192|128]   42.0  | 512/256 33.0 | 1024/256 27.4 | 2048/256 35.5
# A larger block wins until VMEM pushes back (one grid step a head at 1,024
# and 2,048: K and V are fetched once); a strip under 128 rows cannot be cut
# along the lanes, and a taller one feeds the MXU more rows for each block of
# keys it loads but computes more of a diagonal cell's upper half. Head 64
# takes 256 where 128 is 7% faster: every strip is unrolled in the trace,
# and at 128 a cached start of the GPT-2 cell was 2.8 s longer than the
# parent's (10% of it, the bound) for 0.25 ms a layer (`PERF.md` section 6).
# A windowed call (`window` set) takes the same row: what bounds its work is
# the cells inside a block, not the block. Sweep of `probe_flash.py band` on a
# v5e at [1,16384,32|4,128], window 1,024 (chip run PR 33; ms a call forward
# + dQ + dK/dV; the same shape's full causal call 58.88 at 2048/256):
#   512/128 15.59 | 512/256 15.37 | 1024/128 11.77 | 1024/256 11.95
#   1024/512 12.62 | 2048/256 10.91 | 2048/512 11.87
# A block of twice the window wins over one of the window: a block's first
# step fetches and starts once for twice the rows, and the cells of the
# second key block it then skips cost nothing.
# A window a quarter of the block (`probe_flash.py band512` on a v5e at
# [1,8192,36|4,128], window 512: nine query heads a key head; chip run PR 45,
# `p45a`; ms a call forward / dQ / dK/dV and their sum):
#   512/128  2.19 / 1.75 / 1.81 = 5.76 | 512/256  2.40 / 1.58 / 1.85 = 5.84
#   1024/128 1.79 / 1.38 / 1.60 = 4.78 | 1024/256 2.01 / 1.35 / 1.69 = 5.05
#   2048/128 1.65 / 1.27 / 1.58 = 4.51 | 2048/256 1.88 / 1.27 / 1.68 = 4.84
# and the full causal call beside it at [1,8192,24|4,128] (groups of six):
#   512/128 18.91 | 512/256 17.85 | 1024/128 13.61 | 1024/256 12.83
#   2048/128 11.89 | 2048/256 3.31 / 3.70 / 4.65 = 11.66
# The block of 2,048 still wins at a window of 512 (four times the window:
# 5% over 1,024, 20% over 512), so `tile_sizes` takes no window and the row
# stands. Under the window a strip of 128 is 7% ahead of 256 (the forward
# 0.22 ms, dK/dV 0.10: a strip then meets two cells of the band's 512, not
# one and a half), where the full call loses 2% to it: 0.33 ms a layer, 1.0
# ms of that cell's 255 ms step, left to a perf_opt issue (a strip from the
# window would change what `flash.plan` and the driver's `flash_plans` say).
# Heads of 256 (`probe_flash.py gated` on a v5e at [1,16384,16|2,256], causal;
# chip run PR 41, `p41a`; ms a call forward / dQ / dK/dV and their sum; the
# fallback this shape took before, 512 x 512 with no strips, first):
#   512/512  18.45 / 22.46 / 27.75 = 68.65 | 512/128 74.48 | 512/256 69.78
#   1024/128 17.68 / 19.02 / 24.24 = 60.94 | 1024/256 15.44 / 19.10 / 24.41 = 58.94
#   1024/512 14.62 / 19.35 / 24.76 = 58.73 | 2048/128 96.01 | 2048/256 95.47
#   2048/512 13.52 / 38.56 / 46.83 = 98.91
# K and V of a block are twice the bytes of the 128 row: at 2,048 the forward
# still gains (13.5 ms) and both backward kernels lose a factor of 1.9 (VMEM
# pushes back), so the block is 1,024; the strip of 512 is 0.2 ms ahead of
# 256 and unrolls half as many strips in the trace.
# The one backward kernel (`probe_flash.py fused` on a v5e, chip runs PR 47,
# `p47a` / `p47d`; ms a call: forward / fused backward | dQ + dK/dV, the pair
# it replaced, at the same tiles; the table's row first):
#   [1,16384,32,192|128]  1024/256 26.48 / 51.59 | 34.48 + 38.56
#       1024/128 31.25 / 52.50 | 1024/512 24.67 / 52.08 | 512/256 36.17 /
#       58.52 | 2048/256 23.72 / 94.95
#   [1,16384,32|4,128]    2048/256 16.33 / 30.08 | 18.70 + 24.04
#       1024/256 19.12 / 31.95 | 2048/512 15.91 / 30.44 | 2048/128 18.34 / 30.60
#   the same, window 1,024: 2048/256 3.64 / 5.68 | 3.06 + 4.38
#       1024/256 4.34 / 6.07 | 2048/128 3.88 / 5.84 | 1024/128 4.48 / 6.15
#   [1,16384,16|2,256]    1024/512 14.68 / 30.91 | 19.37 + 24.83
#       1024/256 15.50 / 30.57 | 512/256 20.27 / 34.21 | 512/512 18.53 / 34.54
#   [16,1024,12,64]       1024/256 1.376 / 1.883 | 1.137 + 1.393
#       1024/128 1.159 / 1.850 | 512/256 1.577 / 2.009 | 1024/512 1.304 / 1.985
#   [1,8192,36|4,128], window 512: 2048/256 1.94 / 2.36 | 1.32 + 1.74
#       2048/128 1.71 / 2.39 | 1024/128 1.85 / 2.52 | 1024/256 2.07 / 2.39
#   [1,8192,24|4,128]     2048/256 3.37 / 5.91 | 3.74 + 4.71 | 1024/256 3.95 / 6.20
#   [1,4096,32|8,64]      1024/256 1.51 / 2.46 | 1.48 + 1.88 | 1024/128 1.77 / 2.51
#   [4,2048,8|4,128]      2048/256 0.432 / 0.647 | 0.372 + 0.511
#       1024/256 0.495 / 0.712 | 2048/512 0.450 / 0.687
#   [1,8192,32,192|128]   1024/256 7.46 / 14.01 | 9.68 + 10.45
# The fused kernel takes 0.70-0.77 of the pair at every shape and its best
# tiles are the pair's: no row of its own. (Heads of 256 at strip 256 are 0.3
# ms ahead in the backward and 0.8 behind in the forward; head 64 at strip 128
# is 0.25 ms a layer ahead and costs set-up, as above.)
# The same shapes on the folded grid (`_fold`; `probe_flash.py fused` on a
# v5e, chip run PR 57, `p57a`: the parent's tree and this one in one call,
# twice each; ms a call forward / fused backward, the rectangle -> folded):
#   [1,16384,32,192|128]  1024/256 26.45 / 51.58 -> 25.07-25.12 / 50.61-50.62
#       (dQ + dK/dV 34.44 + 38.53 -> 32.45 + 37.79-37.84)
#       1024/128 31.25 / 52.46 -> 29.85-29.92 / 51.46-51.53
#       1024/512 24.65 / 52.06 -> 23.28-23.47 / 51.10-51.43
#       512/256 36.15 / 58.52 -> 32.94-33.17 / 54.67-54.89
#       2048/256 23.73 / 94.95 -> 22.89-22.90 / 68.93-68.94
#   [1,16384,16|2,256]    1024/512 14.67-14.69 / 30.88-30.91 -> 13.81-13.84 /
#       30.37-30.39 | 1024/256 15.51-15.56 / 30.60-30.63 -> 14.62-14.64 /
#       30.05-30.08 | 512/256 20.27-20.29 / 34.24 -> 18.39-18.42 / 32.21
#       512/512 18.53-18.56 / 34.58-34.60 -> 16.66-16.69 / 32.57
#   [1,8192,24|4,128]     2048/256 3.37-3.38 / 5.93-5.97 -> 3.24-3.25 /
#       5.87-5.89 | 1024/256 3.95-3.99 / 6.19-6.23 -> 3.70-3.79 / 6.07-6.08
# A step entered to do nothing costs 0.25-0.45 us (3,840 of them a call at
# 32 heads of n = 16: 1.35 ms forward, 0.97 backward); every row gains and
# the rows keep their order (2,048 at heads of 192 still loses the backward
# to VMEM, by less): no tile changes.
# Differential attention's maps (keys of 64, values of 128: a pair of heads'
# values side by side; `models/transformer.py` `_diff_attend`). Sweep at
# [1,16384,20|10,64|128] on a v5e (my chip run, PR 56; ms a call, forward and
# forward + fused backward; the fallback 512/512 first), full causal:
#   512/512 19.9, 44.3 | 1024/256 11.9, 32.2 | 1024/512 10.5, 31.1
#   2048/256 9.8, 29.2 | 2048/512 9.2, 28.8
# and under a window of 512: 512/512 2.75, 6.42 | 1024/512 2.03, 5.35 |
# 2048/512 1.93, 5.25.
_TILES = {
    (64, 64): (1024, 256),
    (64, 128): (2048, 512),
    (128, 128): (2048, 256),
    (192, 128): (1024, 256),
    (256, 256): (1024, 512),
}
_NEG_INF = -1e30


# ---------------------------------------------------------------- tiles


def tile_sizes(S, D, Dv, dtype, block_q=None, block_k=None, sub=None):
    """(bq, bk, sub) for one call, from the shapes alone: `_TILES`' row for
    the head widths where the caller names no block, `DEFAULT_BLOCK` where
    the table has no row or its block does not divide S. A strip that does
    not divide both blocks is the whole block (no strips)."""
    row = _TILES.get((D, Dv)) if jnp.dtype(dtype).itemsize == 2 else None
    t_block, t_sub = row or (DEFAULT_BLOCK, DEFAULT_BLOCK)
    if S > t_block and S % t_block:
        t_block = DEFAULT_BLOCK
    bq = min(block_q or t_block, S)
    bk = min(block_k or t_block, S)
    sub = min(sub or t_sub, bq, bk)
    if bq % sub or bk % sub:
        sub = max(bq, bk)
    return bq, bk, sub


def _decomposed(causal, bq, bk, sub, seq_len) -> bool:
    """Whether a tile a boundary cuts is taken cell by cell: its place
    relative to the diagonal has to be static, which square blocks that
    divide the sequence give (a tile's class then follows from iq - ik)."""
    return bool(causal and bq == bk and seq_len % bq == 0 and sub < bq)


def _tile_class(iq, ik, *, bq, bk, seq_len, causal, ragged, window=None):
    """(skipped, interior, edge) of grid tile (iq, ik), on Python ints or on
    traced program ids. `ragged` names the side whose padded last block
    needs a mask: "k" in the forward and dQ kernels (padded keys would enter
    every row's softmax), "q" in the dK/dV kernel (padded queries would add
    to every key's gradient), "qk" in the fused backward (both). With a
    `window`, a tile wholly below the band
    (or, on a banded grid, past the last block) is skipped too, and one the
    band's lower boundary cuts is an edge."""
    row0, col0 = iq * bq, ik * bk
    if ragged == "qk":
        inside = (col0 + bk <= seq_len) & (row0 + bq <= seq_len)
        outside = (col0 + bk > seq_len) | (row0 + bq > seq_len)
    else:
        end = col0 + bk if ragged == "k" else row0 + bq
        inside, outside = end <= seq_len, end > seq_len
    if not causal:
        return False, inside, outside
    last_row = row0 + (bq - 1)
    last_col = col0 + (bk - 1)
    if window is None:
        return (col0 > last_row, (last_col <= row0) & inside,
                (col0 <= last_row) & ((last_col > row0) | outside))
    live = (col0 <= last_row) & (row0 - last_col < window) & (row0 < seq_len)
    cut = (last_col > row0) | (last_row - col0 >= window) | outside
    return ((col0 > last_row) | (row0 - last_col >= window)
            | (row0 >= seq_len),
            (last_col <= row0) & (last_row - col0 < window) & inside,
            live & cut)


def _tile_bodies(iq, ik, **tile):
    """`pl.when` for the interior body and for the edge body of grid step
    (iq, ik). A class no tile of the whole grid has (one block a sequence
    has no interior tile; a full, even grid no edge) gets a decorator that
    drops its body: a kernel traces and compiles no body it cannot enter."""
    grid = [_tile_class(i, j, **tile)
            for i in range(pl.cdiv(tile["seq_len"], tile["bq"]))
            for j in range(pl.cdiv(tile["seq_len"], tile["bk"]))]
    here = _tile_class(iq, ik, **tile)
    return [pl.when(here[c]) if any(t[c] for t in grid) else (lambda f: None)
            for c in (1, 2)]


def _edge_tiles(iq, ik, when_edge, b, window, seq_len):
    """[(d, decorator)] of the decomposed edge bodies: the tiles a boundary
    cuts, each known by d = iq - ik. The diagonal alone without a window
    (the edge class is that tile); with one, also the one or two distances
    at which the band's lower boundary crosses a tile (a banded grid's
    steps past the last q block are at such a distance and are no tile)."""
    if window is None:
        return [(0, when_edge)]
    return [(d, pl.when((iq - ik == d) & (iq * b < seq_len)))
            for d in range((window + b - 2) // b + 1)
            if d == 0 or (d + 1) * b > window]


def _cells(d, b, sub, window, fixed, fixed_is_query):
    """Strip `fixed` of one side of edge tile d (blocks of `b`, strips of
    `sub`) against the other side's strips -> (masked, runs): `masked` the
    [(strip, off)] a boundary cuts, query - key = off + (row - column)
    inside the cell, `runs` the [(first strip, strips)] wholly inside the
    band, neighbours joined. Strips outside the band are in neither."""
    masked, runs = [], []
    for o in range(b // sub):
        qs, ks = (fixed, o) if fixed_is_query else (o, fixed)
        off = d * b + (qs - ks) * sub
        lo, hi = off - (sub - 1), off + (sub - 1)
        if hi < 0 or (window is not None and lo >= window):
            continue
        if lo >= 0 and (window is None or hi < window):
            if runs and sum(runs[-1]) == o:
                runs[-1] = (runs[-1][0], runs[-1][1] + 1)
            else:
                runs.append((o, 1))
        else:
            masked.append((o, off))
    return masked, runs


def _cell_masks(cells, sub, window, lower_rows):
    """{off: mask [sub, sub]} of every masked cell of `cells` (a list of
    `_cells` results): query >= key and, with a window, query - key <
    window, queries on the rows (`lower_rows`) or on the columns. Only the
    comparisons a boundary inside the cell needs are built."""
    r = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    q, k = (r, c) if lower_rows else (c, r)
    out = {}
    for off in sorted({off for masked, _ in cells for _, off in masked}):
        m = None
        if off - (sub - 1) < 0:
            m = q >= k if off == 0 else q + off >= k
        if window is not None and off + (sub - 1) >= window:
            w = q + (off - window) < k
            m = w if m is None else m & w
        out[off] = m
    return out


def _band_k(i, bq, bk, window, nk, lo=jnp.maximum, hi=jnp.minimum):
    """(first, last) key block the band of q block i reaches; on traced
    ids, or on ints with `lo=max, hi=min`."""
    return (lo(i * bq - (window - 1), 0) // bk,
            hi(((i + 1) * bq - 1) // bk, nk - 1))


def _band_q(j, bq, bk, window, nq, hi=jnp.minimum):
    """(first, last) q block that sees key block j."""
    return (j * bk) // bq, hi(((j + 1) * bk + window - 2) // bq, nq - 1)


def _band_steps(seq_len, bq, bk, window):
    """(key blocks a q block's band reaches at most, q blocks a key block
    is seen by at most): the inner grid dimensions of a windowed call."""
    nq, nk = pl.cdiv(seq_len, bq), pl.cdiv(seq_len, bk)
    span = lambda first, last: last - first + 1
    return (max(span(*_band_k(i, bq, bk, window, nk, max, min))
                for i in range(nq)),
            max(span(*_band_q(j, bq, bk, window, nq, min))
                for j in range(nk)))


class TilePlan(NamedTuple):
    """What the forward kernel does for one (batch, head), counted in cells
    of `sub` x `sub` where edge tiles are decomposed and in grid tiles
    where they are not. Interior cells run unmasked, edge cells build a
    mask, skipped cells are neither computed nor fetched."""
    bq: int
    bk: int
    sub: int
    tiles_interior: int
    tiles_edge: int
    tiles_skipped: int


def tile_plan(seq_len, bq, bk, sub, causal, window=None) -> TilePlan:
    dec = _decomposed(causal, bq, bk, sub, seq_len)
    n = bq // sub if dec else 1
    interior = edge = skipped = 0
    for iq in range(pl.cdiv(seq_len, bq)):
        for ik in range(pl.cdiv(seq_len, bk)):
            s, i, _ = _tile_class(iq, ik, bq=bq, bk=bk, seq_len=seq_len,
                                  causal=causal, ragged="k", window=window)
            if i:
                interior += n * n
            elif s:
                skipped += n * n
            elif not dec:  # a whole masked tile
                edge += 1
            else:  # a tile a boundary cuts, by cells
                for a in range(n):
                    masked, runs = _cells(iq - ik, bq, sub, window, a, True)
                    full = sum(c for _, c in runs)
                    interior, edge = interior + full, edge + len(masked)
                    skipped += n - full - len(masked)
    return TilePlan(bq, bk, sub if dec else max(bq, bk), interior, edge,
                    skipped)


# What the fused backward may hold in VMEM for dQ beside the kernel's
# present need: the head's float32 accumulator and dQ's double-buffered
# whole-head output block. 64 MiB is S x D (D in whole lanes) of 8 M
# elements in a 2-byte dtype; the widest cell (kanana: 16,384 x 192, held
# in 256 lanes) takes 16 + 16 MiB. A v5e core has 128 MiB.
FUSED_DQ_VMEM_BUDGET = 64 << 20


def _dq_head_bytes(S, bq, D, itemsize):
    """VMEM the fused backward holds for one head's dQ."""
    lanes = -(-D // 128) * 128
    return 4 * pl.cdiv(S, bq) * bq * lanes + 2 * itemsize * S * lanes


def bwd_kind(S, D, Dv, dtype, block_q=None, block_k=None, sub=None) -> str:
    """Which backward a call's shapes take: "fused" (one kernel: dK, dV and
    dQ from one S, dP and exponential) where a head's dQ fits
    `FUSED_DQ_VMEM_BUDGET`, "split" (the dQ kernel and the dK/dV kernel)
    beyond it."""
    itemsize = jnp.dtype(dtype).itemsize
    bq = tile_sizes(S, D, Dv, dtype, block_q, block_k, sub)[0]
    fits = _dq_head_bytes(S, bq, D, itemsize) <= FUSED_DQ_VMEM_BUDGET
    return "fused" if fits else "split"


def _compiler_params(bq, bk, sub, D, Dv, itemsize, dq_head_bytes=0):
    """Grid semantics, and room in VMEM for blocks past the compiler's 16 MiB
    of scoped stack: the double-buffered blocks of the widest kernel
    (dK/dV), its accumulators, and half a dozen [sub, block] float32
    temporaries; in the fused backward a head's dQ besides
    (`dq_head_bytes`), which crosses the key blocks: that grid dimension is
    then sequential too. A v5e core has 128 MiB."""
    blocks = 2 * itemsize * (2 * bq * (D + Dv) + 2 * bk * (D + Dv))
    need = blocks + 4 * bk * (D + Dv) + 6 * 4 * sub * max(bq, bk)
    limit = None
    if need + dq_head_bytes > (12 << 20):
        limit = min(2 * need + dq_head_bytes, 100 << 20)
    outer = "arbitrary" if dq_head_bytes else "parallel"
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", outer, "arbitrary"),
        vmem_limit_bytes=limit)


def _strips(n, sub):
    """Index of each strip of `sub` among `n` positions of a block."""
    if sub >= n or n % sub:
        return [slice(None)]
    return [pl.ds(a * sub, sub) for a in range(n // sub)]


def _dot_nt(a, b):
    """[m, d] x [n, d] -> [m, n]. Dots take the native bf16 operands (MXU
    full rate) and accumulate in f32 via preferred_element_type; casting
    inputs to f32 would drop the MXU to a quarter of its bf16 rate."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nn(a, b):
    """[m, n] x [n, d] -> [m, d], f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """[n, m] x [n, d] -> [m, d], f32 accumulation: the left operand is
    contracted along its rows (dQ from dS^T as the dK/dV kernel holds it)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _folds(scale) -> bool:
    """A power-of-two scale (D = 64: 0.125) multiplies `q` exactly in any
    float type, so it leaves the [bq, bk] score tile for the [bq, D] block:
    the same bits, a `bk / D`-th of the multiplies."""
    return math.frexp(scale)[0] == 0.5


def _scaled(x, scale):
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _edge_mask(iq, ik, bq, bk, seq_len, causal, queries_on_rows,
               window=None, both=False):
    """The mask of a whole edge tile from its place in the sequence:
    [bq, bk] (forward, dQ: keys inside the sequence) or [bk, bq] (dK/dV:
    queries inside it; `both` where that kernel also makes dQ), under the
    diagonal if causal, inside the band if windowed."""
    shape, q_dim = ((bq, bk), 0) if queries_on_rows else ((bk, bq), 1)
    rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
    cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim)
    valid = (cols if queries_on_rows else rows) < seq_len
    if both:
        valid = valid & ((rows if queries_on_rows else cols) < seq_len)
    if window is not None:
        valid = valid & (rows - cols < window)
    return valid & (rows >= cols) if causal else valid


def _zero_padded(block0, n, seq_len, *arrays):
    """Rows of a ragged last block beyond the sequence hold uninitialized
    memory (possibly NaN/inf); a masked p of exactly 0 still yields
    0*NaN=NaN in a dot, so they are zeroed."""
    valid = (block0 + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)) < seq_len
    return [jnp.where(valid, a, jnp.zeros_like(a)) for a in arrays]


# ---------------------------------------------------------------- forward


def _folded(causal, bq, bk, seq_len, window=None) -> bool:
    """Whether a call's grid is the folded one (`_fold`): full causal, the
    blocks square and dividing the sequence into an even number n >= 2.
    From the static shapes alone; anything else takes the rectangle."""
    n = seq_len // bq
    return bool(causal and window is None and bq == bk
                and seq_len % bq == 0 and n >= 2 and n % 2 == 0)


def _fold(p, j, n, keys_inner, where=jnp.where):
    """Step (p, j) of the folded grid n / 2 x (n + 1) -> (iq, ik, the step
    within its row, the row's steps). The causal triangle of n x n blocks
    has i + 1 tiles in row i: row p and row n - 1 - p together have n + 1,
    so the pair is one line of the grid and every step is a tile at or
    below the diagonal. Keys inner (a row is a q block, its key blocks
    ascending): j <= p is (p, j), the rest (n - 1 - p, j - p - 1). Queries
    inner (a row is a key block, the q blocks that see it ascending):
    j < n - p is key block p at q block p + j, the rest key block n - 1 - p
    at q block j - 1. On traced ids, or on ints with a `where` of ints."""
    if keys_inner:
        low = j <= p
        iq, ik = where(low, p, n - 1 - p), where(low, j, j - p - 1)
        return iq, ik, ik, iq + 1
    low = j < n - p
    iq, ik = where(low, p + j, j - 1), where(low, p, n - 1 - p)
    return iq, ik, iq - ik, n - ik


def _grid_dims(causal, bq, bk, seq_len, window, keys_inner):
    """(outer, inner) grid dimensions of one (batch, head): every block of
    the one side by every block of the other; with a window the inner one
    is the blocks a band reaches (`_band_steps`); folded, n / 2 x (n + 1)."""
    nq, nk = pl.cdiv(seq_len, bq), pl.cdiv(seq_len, bk)
    if _folded(causal, bq, bk, seq_len, window):
        return nq // 2, nq + 1
    if window is not None:
        k_steps, q_steps = _band_steps(seq_len, bq, bk, window)
        return (nq, k_steps) if keys_inner else (nk, q_steps)
    return (nq, nk) if keys_inner else (nk, nq)


def grid_steps(seq_len, bq, bk, causal, window=None):
    """(grid steps of one (batch, head) of the forward call, those of them
    that are entered to do nothing: a tile above the diagonal or below the
    band, which a rectangle's inner dimension still runs over)."""
    outer, inner = _grid_dims(causal, bq, bk, seq_len, window, True)
    work = sum(not _tile_class(i, j, bq=bq, bk=bk, seq_len=seq_len,
                               causal=causal, ragged="k", window=window)[0]
               for i in range(pl.cdiv(seq_len, bq))
               for j in range(pl.cdiv(seq_len, bk)))
    return outer * inner, outer * inner - work


def _grid_place(causal, bq, bk, seq_len, window, keys_inner):
    """(iq, ik, step within the row, the row's steps) of this grid step: the
    inner dimension runs over every block of the other side, or with a
    window over those the band reaches, from its first one; on the folded
    grid (`_folded`) a row ends where its tiles do."""
    outer, inner = pl.program_id(2), pl.program_id(3)
    nq, nk = pl.cdiv(seq_len, bq), pl.cdiv(seq_len, bk)
    if _folded(causal, bq, bk, seq_len, window):
        return _fold(outer, inner, nq, keys_inner)
    if window is None:
        n = nk if keys_inner else nq
        pair = (outer, inner) if keys_inner else (inner, outer)
    elif keys_inner:
        n = _band_steps(seq_len, bq, bk, window)[0]
        pair = (outer, _band_k(outer, bq, bk, window, nk)[0] + inner)
    else:
        n = _band_steps(seq_len, bq, bk, window)[1]
        pair = (_band_q(outer, bq, bk, window, nq)[0] + inner, outer)
    return pair + (inner, n)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, seq_len, sub, window):
    iq, ik, step_i, steps = _grid_place(causal, bq, bk, seq_len, window,
                                            True)
    fold = _folds(scale)
    when_interior, when_edge = _tile_bodies(
        iq, ik, bq=bq, bk=bk, seq_len=seq_len, causal=causal, ragged="k",
        window=window)

    @pl.when(step_i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def queries(rows):
        q = q_ref[rows]
        return _scaled(q, scale) if fold else q

    def score(q, k):
        s = _dot_nt(q, k)                     # [rows, keys] f32
        return s if fold else s * scale

    def step(rows, scores, values):
        """One online-softmax update of `rows` from the score pieces of one
        tile and their value rows; only the statistics are carried in f32."""
        m_prev = m_scr[rows]                  # [rows, 1]
        m_new = m_prev
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l = l_scr[rows] * alpha
        acc = acc_scr[rows] * alpha
        for s, v in zip(scores, values):
            p = jnp.exp(s - m_new)
            l = l + jnp.sum(p, axis=1, keepdims=True)
            acc = acc + _dot_nn(p.astype(v.dtype), v)
        m_scr[rows] = m_new
        l_scr[rows] = l
        acc_scr[rows] = acc

    @when_interior
    def _interior():
        k, v = k_ref[...], v_ref[...]
        for rows in _strips(bq, sub):
            step(rows, [score(queries(rows), k)], [v])

    def cut(d):
        """An edge tile at distance d, by cells: each strip of rows meets
        the masked cells, then the runs of keys wholly in the band."""
        cells = [_cells(d, bq, sub, window, a, True)
                 for a in range(bq // sub)]
        masks = _cell_masks(cells, sub, window, lower_rows=True)
        for rows, (masked, runs) in zip(_strips(bq, sub), cells):
            if not masked and not runs:
                continue
            q = queries(rows)
            scores, values = [], []
            for b, off in masked:
                keys = pl.ds(b * sub, sub)
                scores.append(jnp.where(masks[off], score(q, k_ref[keys]),
                                        _NEG_INF))
                values.append(v_ref[keys])
            for b, n in runs:
                keys = pl.ds(b * sub, n * sub)
                scores.append(score(q, k_ref[keys]))
                values.append(v_ref[keys])
            step(rows, scores, values)

    if _decomposed(causal, bq, bk, sub, seq_len):
        for d, when in _edge_tiles(iq, ik, when_edge, bq, window,
                                   seq_len):
            when(functools.partial(cut, d))
    else:
        @when_edge
        def _edge():
            k, v = k_ref[...], v_ref[...]
            if seq_len % bk:
                k, v = _zero_padded(ik * bk, bk, seq_len, k, v)
            valid = _edge_mask(iq, ik, bq, bk, seq_len, causal, True, window)
            s = jnp.where(valid, score(queries(slice(None)), k), _NEG_INF)
            step(slice(None), [s], [v])

    @pl.when(step_i == steps - 1)
    def _flush():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[:] + jnp.log(l_safe)).T


def _stat_spec(bq, index_map):
    """A (1, bq) block of a [B, H, 1, S'] statistic, the sequence on the
    lanes: a float32 [..., S, 1] block stores 128 lanes for each one used."""
    return pl.BlockSpec((None, None, 1, bq), index_map)


def _kv_block(causal, bq, bk, window=None, nk=None):
    """The K/V block of grid step (i, j) of the forward and dQ kernels. A
    skipped step (j beyond the diagonal of q block i) keeps the last needed
    block: the pipeline copies nothing when the index does not move. With a
    window, step j of q block i is the j-th block of its band."""
    if not causal:
        return lambda i, j: j
    if window is None:
        return lambda i, j: jnp.minimum(j, ((i + 1) * bq - 1) // bk)
    def block(i, j):
        first, last = _band_k(i, bq, bk, window, nk)
        return jnp.minimum(first + j, last)
    return block


def _q_block(causal, bq, bk, window=None, nq=None):
    """The same for the Q-side blocks of the dK/dV kernel at (j, i): skipped
    steps come first there and wait on the first needed block. With a
    window, step i of key block j is the i-th q block that sees it, and the
    skipped steps come last."""
    if not causal:
        return lambda j, i: i
    if window is None:
        return lambda j, i: jnp.maximum(i, (j * bk) // bq)
    def block(j, i):
        first, last = _band_q(j, bq, bk, window, nq)
        return jnp.minimum(first + i, last)
    return block


def _block_at(causal, bq, bk, seq_len, window, keys_inner):
    """Two functions of (outer, inner), the q block and the key block the
    index maps of a call's grid fetch and write at a grid step. The
    rectangle's outer id is its own side's block and the inner one is held
    on the needed blocks (`_kv_block`, `_q_block`); the folded grid skips
    nothing (`_fold`)."""
    nq, nk = pl.cdiv(seq_len, bq), pl.cdiv(seq_len, bk)
    if _folded(causal, bq, bk, seq_len, window):
        return (lambda p, j: _fold(p, j, nq, keys_inner)[0],
                lambda p, j: _fold(p, j, nq, keys_inner)[1])
    if keys_inner:
        return lambda i, j: i, _kv_block(causal, bq, bk, window, nk)
    return _q_block(causal, bq, bk, window, nq), lambda j, i: j


def _flash_fwd(q, k, v, scale, causal, block_q=None, block_k=None, sub=None,
               window=None):
    """q: [B,H,S,D], k: [B,KVH,S,D], v: [B,KVH,S,Dv] -> (o [B,H,S,Dv],
    lse [B,H,S] f32). Dv may differ from D (latent attention: keys 192
    wide, values 128). Each traced call publishes its `TilePlan` as one
    `flash.plan` observation (layers under one scan trace once)."""
    from ray_tpu.util import tracing

    B, H, S, D = q.shape
    KVH, Dv = k.shape[1], v.shape[-1]
    group = H // KVH
    bq, bk, sub = tile_sizes(S, D, Dv, q.dtype, block_q, block_k, sub)
    nq = pl.cdiv(S, bq)
    steps, idle = grid_steps(S, bq, bk, causal, window)
    tracing.observe("flash.plan", 0, slow=False, window=window or 0,
                    bwd=bwd_kind(S, D, Dv, q.dtype, block_q, block_k, sub),
                    grid_steps=steps, grid_steps_idle=idle,
                    **tile_plan(S, bq, bk, sub, causal, window)._asdict())
    iq, ik = _block_at(causal, bq, bk, S, window, True)
    q_at = lambda b, h, i, j: (b, h, iq(i, j), 0)
    kv_at = lambda b, h, i, j, g=group: (b, h // g, ik(i, j), 0)
    params = _compiler_params(bq, bk, sub, D, Dv, q.dtype.itemsize)

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, seq_len=S, sub=sub, window=window),
        grid=(B, H) + _grid_dims(causal, bq, bk, S, window, True),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), q_at),
            pl.BlockSpec((None, None, bk, D), kv_at),
            pl.BlockSpec((None, None, bk, Dv), kv_at),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, Dv), q_at),
            _stat_spec(bq, lambda b, h, i, j: (b, h, 0, iq(i, j))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
            # Whole blocks, so that no block is ragged along the lanes.
            jax.ShapeDtypeStruct((B, H, 1, nq * bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=params,
        interpret=dispatch.interpret(),
    )(q, k, v)
    return o, lse[:, :, 0, :S]


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, bq, bk, seq_len, sub, window):
    iq, ik, step_i, steps = _grid_place(causal, bq, bk, seq_len, window,
                                            True)
    fold = _folds(scale)
    when_interior, when_edge = _tile_bodies(
        iq, ik, bq=bq, bk=bk, seq_len=seq_len, causal=causal, ragged="k",
        window=window)

    @pl.when(step_i == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def add(rows, pieces):
        """dq of `rows` from (k, v, mask) pieces of one tile. p comes from
        the saved lse, so the pieces are independent of each other."""
        q, do = q_ref[rows], do_ref[rows]
        if fold:
            q = _scaled(q, scale)
        lse = lse_ref[:, rows].T        # [rows, 1] f32
        delta = delta_ref[:, rows].T
        dq = None
        for k, v, mask in pieces:
            s = _dot_nt(q, k)
            if not fold:
                s = s * scale
            if mask is not None:
                s = jnp.where(mask, s, _NEG_INF)
            ds = jnp.exp(s - lse) * (_dot_nt(do, v) - delta)
            if not fold:
                ds = ds * scale
            part = _dot_nn(ds.astype(k.dtype), k)
            dq = part if dq is None else dq + part
        dq_scr[rows] += dq

    @when_interior
    def _interior():
        k, v = k_ref[...], v_ref[...]
        for rows in _strips(bq, sub):
            add(rows, [(k, v, None)])

    def cut(d):
        cells = [_cells(d, bq, sub, window, a, True)
                 for a in range(bq // sub)]
        masks = _cell_masks(cells, sub, window, lower_rows=True)
        for rows, (masked, runs) in zip(_strips(bq, sub), cells):
            keys = [(pl.ds(b * sub, sub), masks[off]) for b, off in masked]
            keys += [(pl.ds(b * sub, n * sub), None) for b, n in runs]
            if keys:
                add(rows, [(k_ref[ks], v_ref[ks], m) for ks, m in keys])

    if _decomposed(causal, bq, bk, sub, seq_len):
        for d, when in _edge_tiles(iq, ik, when_edge, bq, window,
                                   seq_len):
            when(functools.partial(cut, d))
    else:
        @when_edge
        def _edge():
            k, v = k_ref[...], v_ref[...]
            if seq_len % bk:
                k, v = _zero_padded(ik * bk, bk, seq_len, k, v)
            add(slice(None), [(k, v, _edge_mask(iq, ik, bq, bk, seq_len,
                                                causal, True, window))])

    @pl.when(step_i == steps - 1)
    def _flush():
        dq = dq_scr[:]
        dq_ref[...] = (dq * scale if fold else dq).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *rest,
                scale, causal, bq, bk, seq_len, sub, window):
    """dK and dV of one key block over the query blocks that see it and,
    where the call is the fused backward (`rest` then starts with dQ's
    whole-head output block and ends with its float32 accumulator), dQ
    too: dS^T is in registers here, so dQ costs one more product and no
    second S, dP or exponential. The accumulator holds every query row of
    the head across the head's key blocks (grid dimension 2 is then
    "arbitrary"), is zeroed at the head's first step and written out once,
    scaled and cast, at its last."""
    fused = len(rest) == 4
    dq_ref, dk_scr, dv_scr, dq_scr = rest if fused else (None, *rest, None)
    iq, ik, step_i, steps = _grid_place(causal, bq, bk, seq_len, window,
                                            False)
    fold = _folds(scale)
    when_interior, when_edge = _tile_bodies(
        iq, ik, bq=bq, bk=bk, seq_len=seq_len, causal=causal,
        ragged="qk" if fused else "q", window=window)
    nq, nk = pl.cdiv(seq_len, bq), pl.cdiv(seq_len, bk)

    @pl.when(step_i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if fused:
        @pl.when((step_i == 0) & (ik == 0))
        def _init_head():
            @pl.loop(0, nq)
            def _(c):
                dq_scr[pl.ds(pl.multiple_of(c * bq, bq), bq)] = jnp.zeros(
                    (bq, dq_scr.shape[1]), jnp.float32)

    def add(keys, pieces, padded_keys=False):
        """dk, dv of `keys` from (q, do, lse, delta, mask, rows) pieces of
        one tile, transposed ([keys, queries]): the statistics lie along
        the lanes as they arrive. With a folded scale `q` is `q * scale`,
        which is also what dk's product wants. `rows` are the piece's
        queries in the head, where the fused call adds their dq."""
        k, v = k_ref[keys], v_ref[keys]
        if padded_keys:
            # A padded key's dS is 0, and 0 * NaN is NaN in dQ's sum.
            k, v = _zero_padded(ik * bk, bk, seq_len, k, v)
        dk = dv = None
        for q, do, lse, delta, mask, rows in pieces:
            st = _dot_nt(k, q)                # [keys, queries] f32
            if not fold:
                st = st * scale
            if mask is not None:
                st = jnp.where(mask, st, _NEG_INF)
            pt = jnp.exp(st - lse)
            dst = pt * (_dot_nt(v, do) - delta)
            if not fold:
                dst = dst * scale
            dv_p = _dot_nn(pt.astype(do.dtype), do)
            dk_p = _dot_nn(dst.astype(q.dtype), q)
            dv = dv_p if dv is None else dv + dv_p
            dk = dk_p if dk is None else dk + dk_p
            if fused:
                dq_scr[rows] += _dot_tn(dst.astype(k.dtype), k)
        dk_scr[keys] += dk
        dv_scr[keys] += dv

    def queries(first, n, mask=None):
        """The piece of `n` queries from `first` of this q block."""
        qs = slice(None) if n == bq else pl.ds(first, n)
        q = q_ref[qs]
        rows = fused and pl.ds(
            pl.multiple_of(iq * bq + first, math.gcd(bq, first)), n)
        return (_scaled(q, scale) if fold else q, do_ref[qs],
                lse_ref[:, qs], delta_ref[:, qs], mask, rows)

    @when_interior
    def _interior():
        piece = queries(0, bq)
        for keys in _strips(bk, sub):
            add(keys, [piece])

    def cut(d):
        cells = [_cells(d, bk, sub, window, b, False)
                 for b in range(bk // sub)]
        masks = _cell_masks(cells, sub, window, lower_rows=False)
        for keys, (masked, runs) in zip(_strips(bk, sub), cells):
            qs = [(a * sub, sub, masks[off]) for a, off in masked]
            qs += [(a * sub, n * sub, None) for a, n in runs]
            if qs:
                add(keys, [queries(*piece) for piece in qs])

    if _decomposed(causal, bq, bk, sub, seq_len):
        for d, when in _edge_tiles(iq, ik, when_edge, bk, window,
                                   seq_len):
            when(functools.partial(cut, d))
    else:
        @when_edge
        def _edge():
            q, do, lse, delta, _, rows = queries(0, bq)
            if seq_len % bq:
                # The statistics' padding is zeros (`flash_bwd_core`).
                q, do = _zero_padded(iq * bq, bq, seq_len, q, do)
            mask = _edge_mask(iq, ik, bq, bk, seq_len, causal, False, window,
                              both=fused)
            add(slice(None), [(q, do, lse, delta, mask, rows)],
                padded_keys=bool(fused and seq_len % bk))

    @pl.when(step_i == steps - 1)
    def _flush():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)

    if fused:
        def dq_out(first, n):
            dq = dq_scr[pl.ds(first, n)]
            dq_ref[pl.ds(first, n)] = (dq * scale if fold else dq).astype(
                dq_ref.dtype)

        # the key block a head's grid ends on: the last one, or on the
        # folded grid the later of the pair its last line holds
        end_k = nk // 2 if _folded(causal, bq, bk, seq_len, window) else nk - 1

        @pl.when((step_i == steps - 1) & (ik == end_k))
        def _flush_head():
            @pl.loop(0, seq_len // bq)
            def _(c):
                dq_out(pl.multiple_of(c * bq, bq), bq)
            if seq_len % bq:
                dq_out(seq_len // bq * bq, seq_len % bq)


def _flash_bwd(res, g, scale, causal, block_q, block_k, sub, window):
    q, k, v, o, lse = res
    do = g.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return flash_bwd_core(q, k, v, do, lse, delta, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          sub=sub, window=window)


def flash_bwd_core(q, k, v, do, lse, delta, *, scale, causal,
                   block_q=None, block_k=None, sub=None, window=None):
    """Backward kernels given externally supplied row stats.

    lse/delta are [B,H,S] and may come from a *global* softmax (ring
    attention merges chunk statistics before calling this per chunk) — p is
    recomputed as exp(s - lse), so partial-chunk gradients compose by
    simple accumulation.
    """
    from ray_tpu.util import tracing

    B, H, S, D = q.shape
    KVH, Dv = k.shape[1], v.shape[-1]
    group = H // KVH
    bq, bk, sub = tile_sizes(S, D, Dv, q.dtype, block_q, block_k, sub)
    nq = pl.cdiv(S, bq)
    tiles = dict(scale=scale, causal=causal, bq=bq, bk=bk, seq_len=S, sub=sub,
                 window=window)
    # Whole (1, bq) blocks with zeros behind the sequence: nothing ragged
    # along the lanes, and a padded query's statistics are numbers.
    pad = [(0, 0), (0, 0), (0, 0), (0, nq * bq - S)]
    lse = jnp.pad(lse[:, :, None, :], pad)
    delta = jnp.pad(delta[:, :, None, :], pad)
    itemsize = q.dtype.itemsize
    kind = bwd_kind(S, D, Dv, q.dtype, bq, bk, sub)
    tracing.observe(f"flash.plan.bwd_{kind}", 0, slow=False)
    fused = kind == "fused"
    dq_bytes = _dq_head_bytes(S, bq, D, itemsize) if fused else 0
    args = (q, k, v, do, lse, delta)

    if not fused:
        iq, ik = _block_at(causal, bq, bk, S, window, True)
        q_at = lambda b, h, i, j: (b, h, iq(i, j), 0)
        kv_at = lambda b, h, i, j, g_=group: (b, h // g_, ik(i, j), 0)
        stat_at = lambda b, h, i, j: (b, h, 0, iq(i, j))
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **tiles),
            grid=(B, H) + _grid_dims(causal, bq, bk, S, window, True),
            in_specs=[
                pl.BlockSpec((None, None, bq, D), q_at),
                pl.BlockSpec((None, None, bk, D), kv_at),
                pl.BlockSpec((None, None, bk, Dv), kv_at),
                pl.BlockSpec((None, None, bq, Dv), q_at),
                _stat_spec(bq, stat_at),
                _stat_spec(bq, stat_at),
            ],
            out_specs=pl.BlockSpec((None, None, bq, D), q_at),
            out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
            compiler_params=_compiler_params(bq, bk, sub, D, Dv, itemsize),
            interpret=dispatch.interpret(),
        )(*args)

    # dk/dv per *query* head, then segment-sum over the GQA group in XLA;
    # the fused call's third result is dq, a whole head a block.
    iq, ik = _block_at(causal, bq, bk, S, window, False)
    q_at = lambda b, h, j, i: (b, h, iq(j, i), 0)
    kv_at = lambda b, h, j, i, g_=group: (b, h // g_, ik(j, i), 0)
    stat_at = lambda b, h, j, i: (b, h, 0, iq(j, i))
    out_at = lambda b, h, j, i: (b, h, ik(j, i), 0)
    dq_spec, dq_shape, dq_scr = ([], [], []) if not fused else (
        [pl.BlockSpec((None, None, S, D), lambda b, h, j, i: (b, h, 0, 0))],
        [jax.ShapeDtypeStruct((B, H, S, D), q.dtype)],
        [pltpu.VMEM((nq * bq, D), jnp.float32)])
    grads = pl.pallas_call(
        functools.partial(_dkv_kernel, **tiles),
        grid=(B, H) + _grid_dims(causal, bq, bk, S, window, False),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), q_at),
            pl.BlockSpec((None, None, bk, D), kv_at),
            pl.BlockSpec((None, None, bk, Dv), kv_at),
            pl.BlockSpec((None, None, bq, Dv), q_at),
            _stat_spec(bq, stat_at),
            _stat_spec(bq, stat_at),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, D), out_at),
            pl.BlockSpec((None, None, bk, Dv), out_at),
        ] + dq_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        ] + dq_shape,
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ] + dq_scr,
        compiler_params=_compiler_params(bq, bk, sub, D, Dv, itemsize,
                                         dq_bytes),
        interpret=dispatch.interpret(),
    )(*args)
    dk_h, dv_h = grads[:2]
    if fused:
        dq = grads[2]

    if group > 1:
        dk = dk_h.reshape(B, KVH, group, S, D).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, KVH, group, S, Dv).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, sub, window):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, sub, window)
    return o


# The forward kernel's two outputs, named inside the forward rule so that a
# remat policy can keep them (`save_only_these_names(*RESIDUAL_NAMES)`): the
# backward rule reads exactly these arrays, and a pallas_call is not a dot,
# so a dots-only policy would run the kernel again to get them back.
RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, sub, window):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, sub,
                        window)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])  # [B,H,S], S on the lanes
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, sub, window, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k, sub, window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def use_kernels(platform: str) -> bool:
    """The dispatch rule of `ops/attention.py`, a pure function of what the
    code observes: the kernels on a TPU, at any shape (`tile_sizes`). Under a
    multi-device mesh they run inside a `shard_map` (`dispatch`'s table)."""
    return platform == "tpu"


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, KVH, D]
    v: jax.Array,  # [B, S, KVH, Dv]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    sub: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention in model layout [B, S, H, D] -> [B, S, H, Dv];
    differentiable. The values' width Dv may differ from D. `window` (with
    `causal`): query i sees keys j with 0 <= i - j < window; one that
    reaches the whole sequence is no window. Blocks and strip left unnamed
    come from `tile_sizes` (what the sweep measures by naming them,
    `benchmarks/probe_flash.py`)."""
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is a causal band of at least one key")
        if window >= q.shape[1]:
            window = None
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = _flash(qt, kt, vt, scale, causal, block_q, block_k, sub, window)
    return jnp.swapaxes(ot, 1, 2)
