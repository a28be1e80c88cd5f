"""Attention whose key set is data (learned sparse attention, the "dsa"
mixer of models/transformer.py): an indexer scores every earlier key, each
query keeps the `topk` it ranks highest, softmax attention runs over the kept
set, and the indexer learns from the attention it steers.

    I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])            (s <= t)
    S_t     = the min(t + 1, topk) keys of largest I[t, .], ties to the lower s
    o[t, a] = sum_{s in S_t} softmax_{S_t}(q[t, a] . k[s, g(a)] scale) v[s, g(a)]
    kl[t]   = KL(pbar[t, .] || softmax_{S_t} I[t, .]),  pbar = mean_a of the
              attention's probabilities, a constant of the loss

The way that ships is the thresholded one (ISSUE 54's (b)); the [S, S] scores
are never held. Four Pallas kernels, each under the device scope its metric
reads (chipbench/reduce/scopes.py):

- `_select_kernel` (`dsa.index`): a block of query rows at a time it forms
  the rows' scores against every earlier key in VMEM (never in HBM) and finds
  each row's kept set EXACTLY by counting passes over the block (no sort, no
  approximate top-k). A row's threshold is held between two candidates of
  the scores' 32 bits, `count(key >= lo) >= k > count(key >= hi)`, and a pass
  halves the gap; what the search costs depends on what it observes. A
  sample (an eighth of the columns) gives every row a bracket, and two
  counts over the WHOLE block check it: an end that fails sends its row back
  to the half line the count proved, so exactness never rests on the sample.
  The loop ends once every row's `lo` counts exactly `k` keys (the kept set
  is `key >= lo`; no later kernel reads the threshold) or is the `k`-th
  largest key itself with keys at it left out; only a block with such a row
  settles ties towards the lower index, by a second bisection over the
  index. On random scores at S = 32,768 that is 24 passes a block (2% of
  the blocks tie) where the fixed search ran 33 + 15.
  The selection is written as BITS: `bits[b, t, l]` bit `p` says whether
  key `p * (S / 32) + l` is kept by query `t` (a key tile of any later
  kernel is a `[bq, bk]` block of words and one shift, no movement along
  the lanes). Also the rows' logsumexp of I over the kept set, the kept
  count, and a block's passes and way (`select`).
- `_fwd_kernel`, `_bwd_kernel` (`dsa.core`): flash attention, a (head, tile)
  a grid step, with the mask of a tile read from the bits; the backward is one
  kernel (dK, dV and dQ from one S, dP and exponential, a head's dQ held in
  VMEM across the key blocks, as ops/flash_attention.py's fused one). Square
  tiles, an even number n of them a side (the cell's 32), take that file's
  folded grid (`_fold`, `_attend_grid`): n / 2 x (n + 1) steps a head, each
  a tile at or below the diagonal (528 for the rectangle's 1,024); o, lse,
  dK and dV are the rectangle's bit for bit, dQ to float32 rounding.
- `_index_loss_kernel` (`dsa.index`): kl and its gradient to qi, ki and w in
  one pass over the tiles (pbar from the saved lse of every head, I again),
  run where the loss is made: the backward rule only scales what it kept.

Under `remat_policy` "full" the bits, both logsumexps, o and the loss pass's
outputs are named residuals (`RESIDUAL_NAMES`): the backward re-runs no
selection and no kernel. Every matmul takes the operands' own type (bfloat16
in a cell) and accumulates in float32; scores, thresholds, statistics and the
loss are float32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import dispatch
from ray_tpu.ops.flash_attention import (_NEG_INF, _block_at, _dot_nn,
                                         _dot_nt, _dot_tn, _fold, _folded,
                                         _grid_dims, _stat_spec)

RESIDUAL_NAMES = ("dsa_bits", "dsa_lse_i", "dsa_o", "dsa_lse", "dsa_kl",
                  "dsa_dqi", "dsa_dki", "dsa_dw")
_INT_MIN, _INT_MAX = -2 ** 31, 2 ** 31 - 1
_SAMPLE = 8  # the selection's bracket comes from one column in `_SAMPLE`
_F32 = jnp.float32
_VMEM_LIMIT = 100 << 20  # a v5e core has 128 MiB


class Plan(NamedTuple):
    """The tiles of one call, from S alone: `planes` keys a bit plane (S /
    32), `bq` x `bk` the attention kernels' tile (`bk` divides a plane),
    `rows` x `chunk` the selection's block of query rows and the columns it
    scores and counts at a time."""
    planes: int
    bq: int
    bk: int
    rows: int
    chunk: int


def plan(S: int) -> Plan:
    if S % 32:
        raise ValueError(f"a selection packs 32 keys a word: S = {S} is not "
                         "a multiple of 32")
    planes = S // 32
    # 1,024 x 1,024 from the sweep of benchmarks/probe_dsa.py on a v5e at
    # [1,32768,32|4,128] (chip run, PR 54; ms a call forward / backward):
    #   512x512 147.4 / 181.6 | 1024x512 138.6 / 155.6 | 512x1024 86.7 / 150.4
    #   1024x1024 76.3 / 137.9 | 2048x512 115.4 / 152.2 | 2048x1024 76.6 / 137.3
    # (a key tile cannot pass a plane: 1,024 keys at 32,768). The same on
    # the folded grid (chip run, PR 57, `p57a`: the parent's tree and this
    # one in one call, twice each; the rectangle -> folded):
    #   1024x1024 76.4-77.1 / 137.8-138.6 -> 70.2-70.8 / 133.6-133.7
    #   512x512 147.6-153.3 / 181.6-182.2 -> 132.1-132.4 / 162.9-163.4
    #   2048x1024 (not square: the rectangle in both trees) 76.5-76.9 /
    #   137.3-137.5 -> 76.9-77.0 / 137.7-138.0
    # A step entered to do nothing costs 0.29-0.39 us (15,872 of them a call).
    return Plan(planes, math.gcd(S, 1024), math.gcd(planes, 1024),
                math.gcd(S, 128), math.gcd(S, 512))


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _flip(b):
    """int32 bits of a float32 <-> an int32 whose signed order is the
    float's (its own inverse: the sign bit stays)."""
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _index_scores(qi_ref, w_scr, ki, heads):
    """I of one tile: qi_ref [heads, rows, dI], w_scr [heads, rows, 1], ki
    [dI, n] -> [rows, n] float32 (-0.0 made +0.0: one zero to rank)."""
    acc = None
    for j in range(heads):
        t = w_scr[j] * jnp.maximum(_dot_nn(qi_ref[j], ki), 0.0)
        acc = t if acc is None else acc + t
    return acc + 0.0


def _selected(bits_ref, ik, per_plane):
    """The [bq, bk] mask of key block `ik` from its block of words."""
    return (jnp.right_shift(bits_ref[...], ik // per_plane) & 1) != 0


# ------------------------------------------------------------- selection


def _select_kernel(qi_ref, ki_ref, w_ref, bits_ref, lse_ref, cnt_ref,
                   passes_ref, way_ref, keys_scr, w_scr, *, S, topk, R, ck,
                   planes, heads):
    ib = pl.program_id(1)
    for j in range(heads):
        w_scr[j] = w_ref[j].T
    row = ib * R + _iota((R, 1), 0)
    n_ck = ((ib + 1) * R + ck - 1) // ck  # the chunks at or below the diagonal
    fold = ck // 128 if ck % 128 == 0 else 1  # lane-aligned partial sums

    def cols(c):
        return pl.ds(pl.multiple_of(c * ck, ck), ck)

    def fill(c, _):
        scores = _index_scores(qi_ref, w_scr, ki_ref[:, cols(c)], heads)
        key = _flip(jax.lax.bitcast_convert_type(scores, jnp.int32))
        col = c * ck + _iota((R, ck), 1)
        keys_scr[:, cols(c)] = jnp.where(col <= row, key, _INT_MIN)
        return 0

    jax.lax.fori_loop(0, n_ck, fill, 0)

    def count(pred):
        """Rows' counts [R, 1] float32 of pred(key [R, ck], first column)."""
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

    # The sample: `sw` lanes of every `stride`-th chunk, an eighth of the
    # columns, the lanes' offset moving with the chunk (from c alone).
    sw = 128 if fold > 1 else ck
    stride = max(1, _SAMPLE // (ck // sw))
    n_sm = (n_ck + stride - 1) // stride

    def sample_count(cand):
        """Rows' counts [R, 1] of the sample's keys >= cand."""
        def body(j, acc):
            c = j * stride
            at = c * ck + (j % (ck // sw)) * sw
            hit = (keys_scr[:, pl.ds(pl.multiple_of(at, sw), sw)]
                   >= cand).astype(_F32)
            return acc + (hit if fold > 1 else
                          jnp.sum(hit, axis=1, keepdims=True))
        acc = jax.lax.fori_loop(0, n_sm, body, jnp.zeros(
            (R, sw if fold > 1 else 1), _F32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def middle(lo, hi):  # lo < middle < hi where hi - lo >= 2; wraps as uint32
        return lo + jax.lax.shift_right_logical(hi - lo, 1)

    # Each row's threshold between two candidates, in the keys' int32 order:
    # count(key >= lo) = n_lo >= k_eff > n_hi = count(key >= hi). A row is
    # settled where n_lo IS k_eff (its kept set is key >= lo: no later kernel
    # reads the threshold itself) or where hi = lo + 1 (lo is the k_eff-th
    # largest key, and keys at it are left out: a tie to settle by index).
    k_eff = jnp.minimum(row + 1, topk).astype(_F32)
    n_row = (row + 1).astype(_F32)
    whole = (jnp.full((R, 1), _INT_MIN, jnp.int32), n_row,
             jnp.full((R, 1), _INT_MAX, jnp.int32), jnp.zeros((R, 1), _F32))

    def bracket():
        """lo, n_lo, hi, n_hi from the sample, each end CHECKED by a count
        over the whole block; the passes it took; whether any row's end
        failed (that row then starts from the half line the count proved)."""
        m = sample_count(_INT_MIN + 1)  # the row's columns in the sample
        k_s = k_eff * m / n_row
        # the sample's count of a row's k_eff largest keys is hypergeometric:
        # variance under k_s (1 - m / n); four deviations either way
        dev = 4.0 * jnp.sqrt(k_s * (1.0 - m / n_row)) + 1.0

        def search(st):
            i, lo, hi, live = st
            cand = middle(lo, hi)
            x = sample_count(cand)
            up = (live > 0) & (x >= k_s + dev)
            down = (live > 0) & (x <= k_s - dev)
            lo, hi = jnp.where(up, cand, lo), jnp.where(down, cand, hi)
            live = ((up | down) & (hi - lo != 1)).astype(jnp.int32)
            return i + 1, lo, hi, live

        steps, lo, hi, _ = jax.lax.while_loop(
            lambda st: jnp.max(st[3]) > 0, search,
            (jnp.int32(0), whole[0], whole[2], jnp.ones((R, 1), jnp.int32)))
        n_lo = jnp.where(lo == _INT_MIN, n_row,
                         count(lambda key, _: key >= lo))
        n_hi = count(lambda key, _: key >= hi)
        low, high = n_lo < k_eff, n_hi >= k_eff  # an end on the wrong side
        out = (jnp.where(low, _INT_MIN, jnp.where(high, hi, lo)),
               jnp.where(low, n_row, jnp.where(high, n_hi, n_lo)),
               jnp.where(high, _INT_MAX, jnp.where(low, lo, hi)),
               jnp.where(high, 0.0, jnp.where(low, n_lo, n_hi)))
        missed = jnp.max((low | high).astype(jnp.int32))
        return out + (steps + 1 + 2 * _SAMPLE, missed)

    # No bracket where every row keeps all its keys, nor where the block has
    # too few chunks for a sample to be worth its two checks.
    lo, n_lo, hi, n_hi, units, missed = jax.lax.cond(
        ((ib + 1) * R > topk) & (n_ck >= 2 * stride), bracket,
        lambda: whole + (jnp.int32(0), jnp.int32(0)))

    def unsettled(lo, n_lo, hi):
        return (n_lo != k_eff) & (hi - lo != 1)

    def halve(st):
        i, lo, n_lo, hi, n_hi = st
        cand = middle(lo, hi)
        n = count(lambda key, _: key >= cand)
        up = unsettled(lo, n_lo, hi) & (n >= k_eff)
        down = unsettled(lo, n_lo, hi) & (n < k_eff)
        return (i + 1, jnp.where(up, cand, lo), jnp.where(up, n, n_lo),
                jnp.where(down, cand, hi), jnp.where(down, n, n_hi))

    # (a vector reduced to the scalar that steers the loop)
    steps, tau, n_tau, hi, n_hi = jax.lax.while_loop(
        lambda st: jnp.max(unsettled(st[1], st[2], st[3]).astype(
            jnp.int32)) > 0,
        halve, (jnp.int32(0), lo, n_lo, hi, n_hi))
    units = units + _SAMPLE * steps

    # Of the keys AT tau the first `ties` by index, where a row leaves some
    # out (then hi = tau + 1 and n_hi counts the keys above tau): the largest
    # x below which fewer than `ties` of them lie is the last one's index,
    # bit by bit. Only in a block where some row leaves a tie out.
    exact = n_tau == k_eff
    ties = jnp.where(exact, 0.0, k_eff - n_hi)
    tie = jnp.max(ties) > 0.0
    nbits = max(1, (S - 1).bit_length())

    def halve_index():
        def index_bit(i, x):
            cand = x | jnp.left_shift(jnp.int32(1), nbits - 1 - i)
            n = count(lambda key, c0: (key == tau)
                      & (c0 + _iota((R, ck), 1) < cand))
            return jnp.where(n <= ties - 1.0, cand, x)
        return jax.lax.fori_loop(0, nbits, index_bit,
                                 jnp.zeros((R, 1), jnp.int32))

    last = jnp.where(exact, S, jax.lax.cond(
        tie, halve_index, lambda: jnp.zeros((R, 1), jnp.int32)))
    units = units + _SAMPLE * jnp.where(tie, nbits, 0)

    word = jnp.zeros((R, planes), jnp.int32)
    cnt = jnp.zeros((R, 1), _F32)
    m = jnp.full((R, 1), _NEG_INF, _F32)
    l = jnp.zeros((R, 1), _F32)
    for p in range(32):  # a plane past the diagonal holds stale keys: col <= row
        key = keys_scr[:, p * planes:(p + 1) * planes]
        col = p * planes + _iota((R, planes), 1)
        sel = ((key > tau) | ((key == tau) & (col <= last))) & (col <= row)
        word = word | jnp.left_shift(sel.astype(jnp.int32), p)
        cnt = cnt + jnp.sum(sel.astype(_F32), axis=1, keepdims=True)
        scores = jnp.where(sel, jax.lax.bitcast_convert_type(
            _flip(key), _F32), _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.where(sel, jnp.exp(scores - m_new), 0.0), axis=1,
            keepdims=True)
        m = m_new
    bits_ref[...] = word
    lse_ref[...] = (m + jnp.log(l)).T
    cnt_ref[...] = cnt.T
    passes_ref[...] = jnp.full((1, R), units, jnp.int32)
    way_ref[...] = jnp.full((1, R), jnp.where(
        missed > 0, 2, jnp.where(tie, 1, 0)), jnp.int32)


def select(qi, ki_t, w, topk: int):
    """qi [B,HI,S,dI], ki_t [B,dI,S], w [B,HI,1,S] float32 (the scale
    folded in) -> bits [B,S,S/32] int32, lse_i [B,1,1,S], count [B,1,1,S];
    and, a block of `plan(S).rows` query rows, passes [B,S/rows] int32 (the
    counting passes it ran in units of one `_SAMPLE`-th of a pass: a pass
    over the block's columns is `_SAMPLE`, one over the sample 1) and way
    [B,S/rows] int32 (0 the sample's bracket held and no tie was left out, 1
    the tie search ran, 2 the bracket missed)."""
    B, HI, S, dI = qi.shape
    pn = plan(S)
    R, ck = pn.rows, pn.chunk
    stat = jax.ShapeDtypeStruct((B, 1, 1, S), _F32)
    block = jax.ShapeDtypeStruct((B, 1, 1, S), jnp.int32)
    at = lambda b, i: (b, 0, 0, i)
    with jax.named_scope("dsa.index"):
        bits, lse_i, count, passes, way = pl.pallas_call(
            functools.partial(_select_kernel, S=S, topk=topk, R=R, ck=ck,
                              planes=pn.planes, heads=HI),
            grid=(B, S // R),
            in_specs=[
                pl.BlockSpec((None, HI, R, dI), lambda b, i: (b, 0, i, 0)),
                pl.BlockSpec((None, dI, S), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((None, HI, 1, R), lambda b, i: (b, 0, 0, i)),
            ],
            out_specs=[
                pl.BlockSpec((None, R, pn.planes), lambda b, i: (b, i, 0)),
                _stat_spec(R, at), _stat_spec(R, at), _stat_spec(R, at),
                _stat_spec(R, at),
            ],
            out_shape=[jax.ShapeDtypeStruct((B, S, pn.planes), jnp.int32),
                       stat, stat, block, block],
            scratch_shapes=[pltpu.VMEM((R, S), jnp.int32),
                            pltpu.VMEM((HI, R, 1), _F32)],
            compiler_params=_params("parallel", "arbitrary"),
            name="dsa_select", interpret=dispatch.interpret(),
        )(qi, ki_t, w)
        return bits, lse_i, count, passes[:, 0, 0, ::R], way[:, 0, 0, ::R]


# ------------------------------------------------------------- attention


def _run(body):
    """The decorator of a body every grid step enters (the folded grid's
    tile, where the rectangle's is under a `pl.when`)."""
    body()


def _fwd_kernel(q_ref, k_ref, v_ref, bits_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, bq, bk, per_plane, n):
    iq, ik = pl.program_id(2), pl.program_id(3)
    if n:  # the folded grid of n blocks a side (`_attend_grid`)
        iq, ik = _fold(iq, ik, n, True)[:2]

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @(_run if n else pl.when(ik * bk <= (iq + 1) * bq - 1))
    def _tile():
        # A row whose kept keys all lie in later tiles carries exp(0) of its
        # masked scores until its first kept key arrives; alpha is then 0.
        s = jnp.where(_selected(bits_ref, ik, per_plane),
                      _dot_nt(q_ref[...], k_ref[...]) * scale, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot_nn(p.astype(v_ref.dtype),
                                                  v_ref[...])
        m_scr[:] = m_new

    @pl.when(ik == (iq if n else pl.num_programs(3) - 1))
    def _flush():
        l = l_scr[:]
        o_ref[...] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[:] + jnp.log(l)).T


def _attend_grid(S, bq, bk, keys_inner):
    """The core's grid of one (batch, head), as a full causal flash call's
    (ops/flash_attention.py) -> (n, (outer, inner), iq, ik), the last two
    functions of (outer, inner): the q block and the key block of a grid
    step. Folded (square tiles, an even n of them a side): n / 2 x (n + 1)
    steps, each a tile (`_fold`). Else n = 0 and the rectangle: every block
    of the one side by every block of the other, the inner block held on
    the first or last one a tile needs, so that a step above the diagonal
    fetches nothing."""
    n = S // bq if _folded(True, bq, bk, S) else 0
    return (n, _grid_dims(True, bq, bk, S, None, keys_inner)) + _block_at(
        True, bq, bk, S, None, keys_inner)


def _attend_fwd(q, k, v, bits, scale, tiles=None):
    """q [B,H,S,D], k, v [B,KVH,S,D], bits [B,S,S/32] -> o [B,H,S,D],
    lse [B,H,1,S] float32. `tiles` (bq, bk): a probe's, else `plan`'s."""
    B, H, S, D = q.shape
    g = H // k.shape[1]
    pn = plan(S)
    bq, bk = tiles or (pn.bq, pn.bk)
    per_plane = pn.planes // bk
    n, grid, iq, ik = _attend_grid(S, bq, bk, True)
    q_at = lambda b, h, i, j: (b, h, iq(i, j), 0)
    kv_at = lambda b, h, i, j: (b, h // g, ik(i, j), 0)
    with jax.named_scope("dsa.core"):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk,
                              per_plane=per_plane, n=n),
            grid=(B, H) + grid,
            in_specs=[
                pl.BlockSpec((None, None, bq, D), q_at),
                pl.BlockSpec((None, None, bk, D), kv_at),
                pl.BlockSpec((None, None, bk, D), kv_at),
                pl.BlockSpec((None, bq, bk), lambda b, h, i, j: (
                    b, iq(i, j), ik(i, j) % per_plane)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, bq, D), q_at),
                _stat_spec(bq, lambda b, h, i, j: (b, h, 0, iq(i, j))),
            ],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
                       jax.ShapeDtypeStruct((B, H, 1, S), _F32)],
            scratch_shapes=[pltpu.VMEM((bq, 1), _F32),
                            pltpu.VMEM((bq, 1), _F32),
                            pltpu.VMEM((bq, D), _F32)],
            compiler_params=_params("parallel", "parallel", "parallel",
                                    "arbitrary"),
            name="dsa_fwd", interpret=dispatch.interpret(),
        )(q, k, v, bits)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bits_ref,
                dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, dq_scr, *, scale, bq,
                bk, per_plane, n):
    ik, step = pl.program_id(2), pl.program_id(3)
    if n:  # the folded grid: a key block's steps are the q blocks from its own
        iq, ik, step, nq = _fold(ik, step, n, False)
    else:
        nk, nq = pl.num_programs(2), pl.num_programs(3)
        first = (ik * bk) // bq  # the first query block that sees this key block
        iq = jnp.maximum(step, first)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when((step == 0) & (ik == 0))
    def _init_head():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @(_run if n else pl.when(step >= first))
    def _tile():
        q, do, k = q_ref[...], do_ref[...], k_ref[...]
        s = jnp.where(_selected(bits_ref, ik, per_plane),
                      _dot_nt(q, k) * scale, _NEG_INF)
        p = jnp.exp(s - lse_ref[...].T)
        ds = (p * (_dot_nt(do, v_ref[...]) - delta_ref[...].T) * scale
              ).astype(q.dtype)
        dv_scr[:] = dv_scr[:] + _dot_tn(p.astype(do.dtype), do)
        dk_scr[:] = dk_scr[:] + _dot_tn(ds, q)
        rows = pl.ds(pl.multiple_of(iq * bq, bq), bq)
        dq_scr[rows] = dq_scr[rows] + _dot_nn(ds, k)

    @pl.when(step == nq - 1)
    def _flush():
        dk_ref[...] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[:].astype(dv_ref.dtype)

    # a head's grid ends on its last key block, or folded on the later one
    # of the pair its last line holds
    @pl.when((step == nq - 1) & (ik == (n // 2 if n else nk - 1)))
    def _flush_head():
        dq_ref[...] = dq_scr[:].astype(dq_ref.dtype)


def _attend_bwd(q, k, v, bits, o, lse, do, scale, tiles=None):
    B, H, S, D = q.shape
    KVH = k.shape[1]
    g = H // KVH
    pn = plan(S)
    bq, bk = tiles or (pn.bq, pn.bk)
    per_plane = pn.planes // bk
    with jax.named_scope("dsa.core"):
        delta = jnp.sum(do.astype(_F32) * o.astype(_F32), axis=-1)[:, :, None]
    n, grid, iq, ik = _attend_grid(S, bq, bk, False)
    block = lambda rows, at: pl.BlockSpec((None, None, rows, D), at)
    q_at = lambda b, h, j, i: (b, h, iq(j, i), 0)
    k_at = lambda b, h, j, i: (b, h // g, ik(j, i), 0)
    stat_at = lambda b, h, j, i: (b, h, 0, iq(j, i))
    out_at = lambda b, h, j, i: (b, h, ik(j, i), 0)
    with jax.named_scope("dsa.core"):
        dk, dv, dq = pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, bq=bq, bk=bk,
                              per_plane=per_plane, n=n),
            grid=(B, H) + grid,
            in_specs=[
                block(bq, q_at), block(bk, k_at), block(bk, k_at),
                block(bq, q_at), _stat_spec(bq, stat_at),
                _stat_spec(bq, stat_at),
                pl.BlockSpec((None, bq, bk), lambda b, h, j, i: (
                    b, iq(j, i), ik(j, i) % per_plane)),
            ],
            out_specs=[
                block(bk, out_at), block(bk, out_at),
                block(S, lambda b, h, j, i: (b, h, 0, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((B, H, S, D), q.dtype)] * 3,
            scratch_shapes=[pltpu.VMEM((bk, D), _F32),
                            pltpu.VMEM((bk, D), _F32),
                            pltpu.VMEM((S, D), _F32)],
            compiler_params=_params("parallel", "parallel", "arbitrary",
                                    "arbitrary"),
            name="dsa_bwd", interpret=dispatch.interpret(),
        )(q, k, v, do, lse, delta, bits)
        # dk / dv a *query* head, summed over the group in XLA (as the
        # flash backward's).
        dk = dk.reshape(B, KVH, g, S, D).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(B, KVH, g, S, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


def _whole_call(outs):
    """A kernel's outputs behind a barrier. A layer scan stacks a kept
    residual, and XLA:TPU fuses that update into the Mosaic call that made
    it; the fusion then runs under the compiler's own 16 MiB of scoped VMEM,
    not the call's `vmem_limit_bytes`, and a kernel that holds more (the
    selection's block of scores, the keys' gradient) does not compile
    (compiled for a v5e, PR 54)."""
    return jax.lax.optimization_barrier(tuple(outs))


def _no_grad(x):
    """The cotangent of an integer operand."""
    return np.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attend(q, k, v, bits, scale):
    return tuple(_attend_fwd(q, k, v, bits, scale))


def _attend_vjp_fwd(q, k, v, bits, scale):
    o, lse = _attend_fwd(q, k, v, bits, scale)
    o = checkpoint_name(o, "dsa_o")
    lse = checkpoint_name(lse, "dsa_lse")
    return (o, lse), (q, k, v, bits, o, lse)


def _attend_vjp_bwd(scale, res, cts):
    q, k, v, bits, o, lse = res
    dq, dk, dv = _attend_bwd(q, k, v, bits, o, lse, cts[0].astype(q.dtype),
                             scale)
    return dq, dk, dv, _no_grad(bits)


_attend.defvjp(_attend_vjp_fwd, _attend_vjp_bwd)


# ----------------------------------------------------------- the indexer's loss


def _index_loss_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, bits_ref,
                       lse_i_ref, kl_ref, dqi_ref, dw_ref, dki_ref, lse_scr,
                       w_scr, kl_scr, dqi_scr, dw_scr, *, scale, bq, bk,
                       per_plane, heads, group, index_heads):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        for a in range(heads):
            lse_scr[a] = lse_ref[a].T
        for j in range(index_heads):
            w_scr[j] = w_ref[j].T
        kl_scr[:] = jnp.zeros_like(kl_scr)
        dqi_scr[:] = jnp.zeros_like(dqi_scr)
        dw_scr[:] = jnp.zeros_like(dw_scr)

    @pl.when((ik == 0) & (iq == 0))
    def _init_keys():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(ik * bk <= (iq + 1) * bq - 1)
    def _tile():
        sel = _selected(bits_ref, ik, per_plane)
        pbar = None
        for a in range(heads):
            p = jnp.exp(_dot_nt(q_ref[a], k_ref[a // group]) * scale
                        - lse_scr[a])
            pbar = p if pbar is None else pbar + p
        pbar = jnp.where(sel, pbar * (1.0 / heads), 0.0)
        ki = ki_ref[...]
        logq = _index_scores(qi_ref, w_scr, ki, index_heads) - lse_i_ref[...].T
        kl_scr[:] = kl_scr[:] + jnp.sum(jnp.where(sel, pbar * (
            jnp.log(jnp.maximum(pbar, 1e-30)) - logq), 0.0), axis=1,
            keepdims=True)
        d_i = jnp.where(sel, jnp.exp(logq) - pbar, 0.0)
        dki = None
        for j in range(index_heads):
            z = _dot_nn(qi_ref[j], ki)
            dw_scr[j] = dw_scr[j] + jnp.sum(d_i * jnp.maximum(z, 0.0), axis=1,
                                            keepdims=True)
            dz = jnp.where(z > 0.0, d_i * w_scr[j], 0.0).astype(ki.dtype)
            dqi_scr[j] = dqi_scr[j] + _dot_nt(dz, ki)
            part = _dot_tn(qi_ref[j], dz)
            dki = part if dki is None else dki + part
        cols = pl.ds(pl.multiple_of(ik * bk, bk), bk)
        dki_ref[:, cols] = dki_ref[:, cols] + dki

    @pl.when(ik == pl.num_programs(2) - 1)
    def _flush():
        kl_ref[...] = kl_scr[:].T
        dqi_ref[...] = dqi_scr[:]
        for j in range(index_heads):
            dw_ref[j] = dw_scr[j].T


def _index_loss_call(q, k, lse, qi, ki_t, w, bits, lse_i, scale):
    """-> kl [B,1,1,S], dqi [B,HI,S,dI], dw [B,HI,1,S], dki_t [B,dI,S], all
    float32: the rows' KL and its gradient to qi, w and ki_t."""
    B, H, S, D = q.shape
    KVH, (HI, dI) = k.shape[1], qi.shape[1::2]
    pn = plan(S)
    bq, bk = min(pn.bq, 256), pn.bk
    per_plane = pn.planes // bk
    kv = lambda i, j: jnp.minimum(j, ((i + 1) * bq - 1) // bk)
    with jax.named_scope("dsa.index"):
        return pl.pallas_call(
            functools.partial(
                _index_loss_kernel, scale=scale, bq=bq, bk=bk,
                per_plane=per_plane, heads=H, group=H // KVH, index_heads=HI),
            grid=(B, S // bq, S // bk),
            in_specs=[
                pl.BlockSpec((None, H, bq, D), lambda b, i, j: (b, 0, i, 0)),
                pl.BlockSpec((None, KVH, bk, D),
                             lambda b, i, j: (b, 0, kv(i, j), 0)),
                pl.BlockSpec((None, H, 1, bq), lambda b, i, j: (b, 0, 0, i)),
                pl.BlockSpec((None, HI, bq, dI), lambda b, i, j: (b, 0, i, 0)),
                pl.BlockSpec((None, dI, bk), lambda b, i, j: (b, 0, kv(i, j))),
                pl.BlockSpec((None, HI, 1, bq), lambda b, i, j: (b, 0, 0, i)),
                pl.BlockSpec((None, bq, bk), lambda b, i, j:
                             (b, i, kv(i, j) % per_plane)),
                _stat_spec(bq, lambda b, i, j: (b, 0, 0, i)),
            ],
            out_specs=[
                _stat_spec(bq, lambda b, i, j: (b, 0, 0, i)),
                pl.BlockSpec((None, HI, bq, dI), lambda b, i, j: (b, 0, i, 0)),
                pl.BlockSpec((None, HI, 1, bq), lambda b, i, j: (b, 0, 0, i)),
                pl.BlockSpec((None, dI, S), lambda b, i, j: (b, 0, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((B, 1, 1, S), _F32),
                       jax.ShapeDtypeStruct((B, HI, S, dI), _F32),
                       jax.ShapeDtypeStruct((B, HI, 1, S), _F32),
                       jax.ShapeDtypeStruct((B, dI, S), _F32)],
            scratch_shapes=[pltpu.VMEM((H, bq, 1), _F32),
                            pltpu.VMEM((HI, bq, 1), _F32),
                            pltpu.VMEM((bq, 1), _F32),
                            pltpu.VMEM((HI, bq, dI), _F32),
                            pltpu.VMEM((HI, bq, 1), _F32)],
            compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
            name="dsa_index_loss", interpret=dispatch.interpret(),
        )(q, k, lse, qi, ki_t, w, bits, lse_i)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _index_loss(qi, ki_t, w, q, k, lse, bits, lse_i, scale):
    return _index_loss_call(q, k, lse, qi, ki_t, w, bits, lse_i, scale)[0]


def _index_loss_vjp_fwd(qi, ki_t, w, q, k, lse, bits, lse_i, scale):
    kept = _whole_call(_index_loss_call(q, k, lse, qi, ki_t, w, bits, lse_i,
                                        scale))
    kl, dqi, dw, dki = map(checkpoint_name, kept, RESIDUAL_NAMES[4:])
    return kl, (dqi.astype(qi.dtype), dki.astype(ki_t.dtype), dw,
                (q, k, lse, bits, lse_i))


def _index_loss_vjp_bwd(scale, res, ct):
    dqi, dki, dw, (q, k, lse, bits, lse_i) = res
    zeros = (jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(lse),
             _no_grad(bits), jnp.zeros_like(lse_i))
    # ct [B,1,1,S] is a row's weight in the loss: it scales the row's
    # gradients; dki sums over rows, so it takes the rows' common weight.
    row = ct[:, 0, 0]
    with jax.named_scope("dsa.index"):
        return ((dqi * row[:, None, :, None].astype(dqi.dtype)),
                (dki * row[:, None, :1].astype(dki.dtype)), dw * ct) + zeros


_index_loss.defvjp(_index_loss_vjp_fwd, _index_loss_vjp_bwd)


# ----------------------------------------------------------------- public


def sparse_attention(q, k, v, qi, ki, w, *, topk: int, scale: float):
    """Model layout: q [B,S,H,D], k, v [B,S,KVH,D], the indexer's qi
    [B,S,HI,dI], ki [B,S,dI] and w [B,S,HI] (float32, 1 / sqrt(HI dI)
    folded in) -> (o [B,S,H,D]; kl [B,S] float32, the rows' KL(pbar ||
    softmax over the kept set of I), differentiable in qi, ki and w ONLY
    and with every row's cotangent the same (a mean over rows: the keys'
    gradient is summed over rows before it is weighed); count [B,S] float32
    of kept keys; bits [B,S,S/32] int32, the selection; `select`'s passes and
    way, a block of query rows). o is differentiable in q, k and v."""
    B, S, H, D = q.shape
    if not dispatch.interpret() and plan(S).bk % 128:
        raise ValueError(
            "on the TPU a key tile of the selection is whole 128-lane words: "
            f"S = {S} is not a multiple of 4,096")
    sg = jax.lax.stop_gradient
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    qi_t = jnp.swapaxes(qi, 1, 2)                       # [B,HI,S,dI]
    ki_t = jnp.swapaxes(ki, 1, 2)                       # [B,dI,S]
    w_t = jnp.swapaxes(w.astype(_F32), 1, 2)[:, :, None]  # [B,HI,1,S]
    bits, lse_i, count, passes, way = _whole_call(select(
        sg(qi_t), sg(ki_t), sg(w_t), topk))
    bits = checkpoint_name(bits, "dsa_bits")
    lse_i = checkpoint_name(lse_i, "dsa_lse_i")
    o, lse = _attend(qt, kt, vt, bits, scale)
    kl = _index_loss(qi_t, ki_t, w_t, sg(qt), sg(kt), sg(lse), bits, lse_i,
                     scale)
    return (jnp.swapaxes(o, 1, 2), kl[:, 0, 0], count[:, 0, 0], bits, passes,
            way)


def mask_of(bits: jax.Array) -> jax.Array:
    """bits [..., S, S/32] int32 -> the selection as booleans [..., S, S]
    (tests and the benchmark's comparison; never in a step)."""
    planes = bits.shape[-1]
    m = (bits[..., None, :] >> jnp.arange(32, dtype=jnp.int32)[:, None]) & 1
    return m.reshape(bits.shape[:-1] + (32 * planes,)) != 0
