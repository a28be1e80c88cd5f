"""The Mamba-2 core (state-space duality, SSD): a selective state-space layer
with one scalar decay a head.

Per head, with a state S [P, N] (P the head's width, N the state's), inputs
x_t [P], a time step dt_t > 0, A < 0, and B_t, C_t [N] shared by the heads of
a group:

    S_t = a_t S_{t-1} + dt_t x_t B_t^T,   a_t = exp(dt_t A)
    y_t = S_t C_t + D x_t

`ssd_recurrent` is that recurrence, one token at a time (the oracle of the
tests, and the shape a decode step will take). The training path runs the
chunked form, `ssd_chunked`: the sequence is cut into chunks of `chunk`
tokens; with G_t = cumsum(dt_t A) from the chunk's start (float32),

    Y_intra = ((C B^T) * L * dt) X,   L[i, j] = exp(G_i - G_j), i >= j, else 0
    state_n = sum_j exp(G_last - G_j) dt_j x_j B_j^T            (a chunk's own)
    S <- exp(G_last) S + state_n                                 (lax.scan)
    Y_inter = exp(G_i) (S_prev C_i)

G_i - G_j is masked BEFORE the exp: above the diagonal it is positive and
overflows float32 once a chunk decays by more than e^88. Below it every
factor is at most 1, so there is no cap and no sub-block (ops/kda.py needs
both for its per-channel decay, which cannot be taken as one difference a
pair of tokens). C B^T is computed once a group, not a head. The chunks'
states go through a scan whose body is one multiply-add: nothing of
[chunks, chunks] is formed.

Precision, as ops/kda.py states its own: the matmuls take operands in x's
dtype (bfloat16 on the chip) and accumulate in float32; dt, A, G, L, the
decay factors and the state are float32.

`ssd_chunked` is the entry point and dispatches on what it observes
(`use_kernels`, no knob), as ops/kda.py does:

- **`ssd_chunked_pallas`**: two Pallas (Mosaic) kernels under a
  `jax.custom_vjp`, on a TPU with no multi-device mesh, when the chunk and
  the state are whole 128-lane tiles and the heads of a group fill 128-lane
  blocks. Grid (batch, chunk, head block), the chunk axis sequential and the
  head blocks inside it: every head's state [H*P, N] float32 lives in VMEM
  scratch across the chunks, so that what the heads of a group share is done
  once a chunk and not once a head block: B and C are fetched and C B^T
  formed at a group's first head block, and dB and dC (sums over a group's
  heads) gather in VMEM and are written once. x is read where it lies:
  [B,S,H,P] is [B,S,H*P] and a head block is a column block of it (no
  [B,H,S,P] copies); inside it heads narrower than 128 lanes are worked in
  lane groups of 128: the state products take a group's heads in one MXU
  pass (their states stacked along the rows), the [C, C] products a head at
  a time with the other heads' lanes zeroed. G = cumsum(dt A) is taken
  OUTSIDE the kernels (a float32 product with a triangle of ones, a
  megabyte a layer), and G and dt come in with the tokens on the lanes
  ([B, blocks, hb, S]: a [1, C] row a head); a grid step transposes its
  [hb, C] block once (XLU) to have a [C, 1] column a head as well, so that
  L[i, j] = exp(G_i - G_j) is a broadcast difference. The backward hands
  dG and ddt back on the same rows, and autodiff takes them through the
  relayout and the cumulative sum: dt's and A's gradients are float32 all
  the way. The forward writes y, the final state and, for the backward,
  the chunk-start states in the compute type (they enter only matmuls).
  The backward walks the chunks in reverse with the states' cotangent in
  scratch, recomputes L and m in VMEM, and sums E = dM * m along its rows
  and its columns for dG (the two sums of one array: their rounding errors
  cancel in dA as the terms do). Nothing [C, C] reaches HBM in either pass.
  `RESIDUAL_NAMES` names y and the chunk-start states (`checkpoint_name` in
  the forward rule) so that a remat policy keeps them and the forward
  kernel runs once (`models/transformer.py` `layer_scan_body`).
- **`ssd_chunked_xla`**: the same algorithm in plain XLA, differentiable by
  autodiff: the path on the CPU (every tier-1 model test), for narrow states
  or chunks, head counts that fill no lane block, and under a mesh (a Mosaic
  call there needs `shard_map`; no configuration trains a Mamba-2 stack on a
  mesh), and the kernels' oracle in the tests beside `ssd_recurrent`. The two
  share no logic beyond padding.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def ssd_recurrent(x, dt, A, B, C, D, *,
                  initial_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Token-by-token SSD. x [B,S,H,P]; dt [B,S,H] (after softplus); A, D
    [H]; B, C [B,S,G,N], G dividing H -> (y [B,S,H,P] in x's dtype, state
    [B,H,P,N] f32)."""
    Bz, S, H, P = x.shape
    G, N = B.shape[-2:]
    s0 = (jnp.zeros((Bz, H, P, N), _F32) if initial_state is None
          else initial_state.astype(_F32))
    per_head = lambda a: jnp.repeat(a, H // G, axis=-2)  # [.., G, N] -> [.., H, N]
    A, D = A.astype(_F32), D.astype(_F32)

    def step(s, t):
        xt, dtt, bt, ct = t                          # [B,H,P] [B,H] [B,H,N] x2
        s = (s * jnp.exp(dtt * A)[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", s, ct, precision=_HI)
        return s, y + D[:, None] * xt

    ts = tuple(jnp.moveaxis(a.astype(_F32), 1, 0)
               for a in (x, dt, per_head(B), per_head(C)))
    s, y = jax.lax.scan(step, s0, ts)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), s


def _pad_to_chunks(x, dt, B, C, chunk):
    """The sequence padded at its end to a multiple of `chunk` with tokens
    that leave the state as it is (dt = 0: no decay, no input)."""
    pad = (-x.shape[1]) % chunk
    if not pad:
        return x, dt, B, C
    x, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
               for a in (x, B, C))
    return x, jnp.pad(dt, ((0, 0), (0, pad), (0, 0))), B, C


def ssd_chunked_xla(x, dt, A, B, C, D, *, chunk: int = 256,
                    initial_state: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD in plain XLA, any sequence length, differentiable by
    autodiff: the fallback of `ssd_chunked` and the kernels' oracle. Same
    arguments and results as `ssd_recurrent`."""
    Bz, S, H, P = x.shape
    G, N = B.shape[-2:]
    R, mm = H // G, x.dtype
    x, dt, B, C = _pad_to_chunks(x, dt, B, C, chunk)
    n = x.shape[1] // chunk

    xc = x.reshape(Bz, n, chunk, G, R, P)
    dtc = dt.astype(_F32).reshape(Bz, n, chunk, G, R)
    Bc = B.astype(mm).reshape(Bz, n, chunk, G, N)
    Cc = C.astype(mm).reshape(Bz, n, chunk, G, N)
    g = jnp.cumsum(dtc * A.astype(_F32).reshape(G, R), axis=2)   # [b,n,c,g,r]
    gl = g[:, :, -1]                                             # [b,n,g,r]

    # Inside a chunk.
    t = jnp.arange(chunk)
    gt = jnp.moveaxis(g, 2, -1)                                  # [b,n,g,r,c]
    L = jnp.exp(jnp.where(t[:, None] >= t[None, :],
                          gt[..., :, None] - gt[..., None, :], -jnp.inf))
    cb = jnp.einsum("bnigs,bnjgs->bngij", Cc, Bc,
                    preferred_element_type=_F32)
    m = (cb[:, :, :, None] * L
         * jnp.moveaxis(dtc, 2, -1)[..., None, :]).astype(mm)    # [b,n,g,r,i,j]
    y = jnp.einsum("bngrij,bnjgrp->bnigrp", m, xc,
                   preferred_element_type=_F32)

    # Each chunk's own state, then the states at the chunks' starts.
    xw = (xc.astype(_F32) * (jnp.exp(gl[:, :, None] - g) * dtc)[..., None]
          ).astype(mm)
    own = jnp.einsum("bnjgrp,bnjgs->bngrps", xw, Bc,
                     preferred_element_type=_F32)
    s0 = (jnp.zeros((Bz, G, R, P, N), _F32) if initial_state is None
          else initial_state.astype(_F32).reshape(Bz, G, R, P, N))

    def carry(s, c):
        decay, own_n = c
        return s * decay[..., None, None] + own_n, s

    s, starts = jax.lax.scan(
        carry, s0, (jnp.moveaxis(jnp.exp(gl), 1, 0), jnp.moveaxis(own, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1).astype(mm)               # [b,n,g,r,p,s]
    y = y + jnp.exp(g)[..., None] * jnp.einsum(
        "bnigs,bngrps->bnigrp", Cc, starts, preferred_element_type=_F32)
    y = y + D.astype(_F32).reshape(G, R)[:, :, None] * xc.astype(_F32)
    y = y.reshape(Bz, n * chunk, H, P)[:, :S]
    return y.astype(mm), s.reshape(Bz, H, P, N)


# ------------------------------------------------------------ Pallas kernels
#
# A grid step is one chunk of one head block. Names below: C the chunk, P a
# head's width, N the state's, hb the heads of a block, W the lanes of a lane
# group (128, or P where a head is wider) and hp = W / P its heads.

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))
_LANES = 512                 # a head block's width in lanes, at most
_STATE_BYTES = 4 << 20       # every head's state in VMEM scratch, at most
_VMEM_BYTES = 48 << 20       # of a v5e's 128 MiB; float32 operands pass 16

# What `_vjp_fwd` keeps besides its own inputs, named so that a remat policy
# can keep them (`models/transformer.py` `layer_scan_body`): y because the
# layer's recomputation needs it, the chunk-start states because the backward
# kernel reads them. A pallas_call is no dot: under a dots-only policy the
# forward kernel would run twice.
RESIDUAL_NAMES = ("ssd_y", "ssd_states")


def _dot(a, b, dims=_NN):
    """MXU product, operands as they come, float32 accumulation."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def heads_per_step(R: int, P: int) -> int:
    """Heads of one group a grid step takes: whole lane groups, doubled
    while they divide the group's R heads and fit `_LANES`."""
    hb = max(1, 128 // P)
    while R % (2 * hb) == 0 and 2 * hb * P <= _LANES:
        hb *= 2
    return hb


def _columns(rows, t_scr):
    """[hb, C] (a row a head, as G and dt come in) -> [C, 128]: head j's
    values down lane j. Through a [128, C] scratch (zeroed by the caller), so
    that what the XLU transposes is whole tiles."""
    t_scr[0:rows.shape[0], :] = rows
    return jnp.transpose(t_scr[...])


def _own(a, lane, k):
    """`a` [C, W] on head k's lanes of a lane group, zero on the others'
    (`lane` [1, W]: the lanes' head, None where a group is one head)."""
    return a if lane is None else jnp.where(lane == k, a, 0.0)


def _spread(blk, heads, which):
    """Per-head values over a lane group: `blk` [C, 128] (a column a head)
    with `which` [1, W] the lanes' head -> [C, W], or `blk` [1, 128] with
    `which` [W, 1] the rows' head -> [W, 1]. One head: its column as it is
    (it broadcasts)."""
    out = blk[:, heads[0]:heads[0] + 1]
    for k, j in enumerate(heads[1:], 1):
        out = jnp.where(which == k, blk[:, j:j + 1], out)
    return out


def _step_terms(gr, dtr, t_scr):
    """From a block's G and dt [hb, C], with the heads on the lanes: G
    [C, 128], exp(G_i), exp(G_last - G_j), that times dt_j, and exp(G_last)
    [1, 128]."""
    t_scr[...] = jnp.zeros_like(t_scr)
    gc, dtc = _columns(gr, t_scr), _columns(dtr, t_scr)
    gl = gc[-1:, :]
    egl = jnp.exp(gl - gc)
    return gc, jnp.exp(gc), egl, egl * dtc, jnp.exp(gl)


def _decay_tile(gc, gr, j, tri):
    """Head j's L: exp(G_i - G_j) on and below the diagonal, masked BEFORE
    the exp (above it the difference is positive and overflows)."""
    return jnp.exp(jnp.where(tri, gc[:, j:j + 1] - gr[j:j + 1, :], -jnp.inf))


def _fwd_kernel(x_ref, b_ref, c_ref, gr_ref, dtr_ref, d_ref, s0_ref,
                y_ref, sf_ref, st_ref, s_scr, cb_scr, t_scr, *, P, W, blocks):
    n, h = pl.program_id(1), pl.program_id(2)
    hb, C = gr_ref.shape[2:]
    hp, mm = W // P, x_ref.dtype

    @pl.when(n == 0)
    def _init():
        s_scr[h] = s0_ref[0]

    bm, cm = b_ref[0], c_ref[0]

    @pl.when(h % blocks == 0)
    def _group():
        cb_scr[...] = _dot(cm, bm, _NT)

    st_ref[0, 0] = s_scr[h].astype(mm)
    gr, dtr = gr_ref[0, 0], dtr_ref[0, 0]
    gc, eg, _, w, dec = _step_terms(gr, dtr, t_scr)
    tri = _iota((C, C), 0) >= _iota((C, C), 1)
    lane = _iota((1, W), 1) // P if hp > 1 else None    # the lanes' head
    row = _iota((W, 1), 0) // P                         # a state row's
    for q in range(hb // hp):
        sl, heads = slice(q * W, (q + 1) * W), range(q * hp, (q + 1) * hp)
        xg = x_ref[0, :, sl]
        xf = xg.astype(_F32)
        s = s_scr[h, sl, :]
        y = _spread(eg, heads, lane) * _dot(cm, s.astype(mm), _NT) \
            + d_ref[:, sl] * xf
        for k, j in enumerate(heads):
            m = (cb_scr[...] * _decay_tile(gc, gr, j, tri)
                 * dtr[j:j + 1, :]).astype(mm)
            y = y + _own(_dot(m, xg), lane, k)
        y_ref[0, :, sl] = y.astype(mm)
        xw = (xf * _spread(w, heads, lane)).astype(mm)
        s_scr[h, sl, :] = s * _spread(dec, heads, row) + _dot(xw, bm, _TN)

    @pl.when(n == pl.num_programs(1) - 1)
    def _flush():
        sf_ref[0] = s_scr[h]


def _bwd_kernel(x_ref, b_ref, c_ref, gr_ref, dtr_ref, d_ref, dsf_ref, st_ref,
                dy_ref, dx_ref, db_ref, dc_ref, dgr_ref, ddtr_ref, dd_ref,
                ds0_ref, ds_scr, cb_scr, t_scr, dcb_scr, db_scr, dc_scr, *,
                P, W, blocks):
    """The reverse sweep's grid step: ds_scr[h] holds the cotangent of the
    block's states at the chunk's end on entry and at its start on exit.
    The casts to the compute type pass cotangents through as autodiff's
    `astype` does. What is summed along a head's row (its share of dG_i, and
    of ddt_j through the state) gathers a column a head in [C, 128] and is
    transposed once onto the rows G and dt came in."""
    n, h = pl.program_id(1), pl.program_id(2)
    hb, C = gr_ref.shape[2:]
    hp, mm = W // P, x_ref.dtype

    @pl.when(n == 0)
    def _init():
        ds_scr[h] = dsf_ref[0]

    bm, cm = b_ref[0], c_ref[0]

    @pl.when(h % blocks == 0)
    def _group():
        cb_scr[...] = _dot(cm, bm, _NT)
        dcb_scr[...] = jnp.zeros_like(dcb_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    gr, dtr = gr_ref[0, 0], dtr_ref[0, 0]
    gc, eg, egl, w, dec = _step_terms(gr, dtr, t_scr)
    tri = _iota((C, C), 0) >= _iota((C, C), 1)
    lane = _iota((1, W), 1) // P if hp > 1 else None    # the lanes' head
    row = _iota((W, 1), 0) // P                         # a state row's
    last, head = _iota((C, 1), 0) == C - 1, _iota((1, 128), 1)
    head_row = _iota((hb, 1), 0)
    dgc, ddtc = jnp.zeros((C, 128), _F32), jnp.zeros((C, 128), _F32)
    dgr, ddtr = jnp.zeros((hb, C), _F32), jnp.zeros((hb, C), _F32)
    dcb, db, dc = 0.0, 0.0, 0.0
    for q in range(hb // hp):
        sl, heads = slice(q * W, (q + 1) * W), range(q * hp, (q + 1) * hp)
        xg, dyg = x_ref[0, :, sl], dy_ref[0, :, sl]
        xf, dyf = xg.astype(_F32), dyg.astype(_F32)
        sm = st_ref[0, 0, sl, :]                     # the chunk-start states
        ds1 = ds_scr[h, sl, :]
        ds1m = ds1.astype(mm)
        egw, ww = _spread(eg, heads, lane), _spread(w, heads, lane)
        dd_ref[0, 0, :, sl] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        # y_inter = exp(G_i) (C_i S0^T);  S1 = exp(G_last) S0 + (x w)^T B.
        dwm = (dyf * egw).astype(mm)
        dc = dc + _dot(dwm, sm)
        ds_scr[h, sl, :] = (_dot(dwm, cm, _TN)
                            + ds1 * _spread(dec, heads, row))
        gy = dyf * _dot(cm, sm, _NT) * egw           # d exp(G_i) exp(G_i)
        dxw = _dot(bm, ds1m, _NT)
        db = db + _dot((xf * ww).astype(mm), ds1m)
        dx = d_ref[:, sl] * dyf + dxw * ww
        gw = dxw * xf                                # dw, summed over a head
        gs = ds1 * sm.astype(_F32)                   # d exp(G_last), likewise
        for k, j in enumerate(heads):
            mine = lambda a, k=k: _own(a, lane, k)
            dyk = mine(dyf).astype(mm)
            dtj, cb = dtr[j:j + 1, :], cb_scr[...]
            lm = _decay_tile(gc, gr, j, tri)
            m = (cb * lm * dtj).astype(mm)
            t = _dot(dyk, xg, _NT) * lm                  # dM L
            dcb = dcb + t * dtj
            e = t * cb                                   # dM (C B^T) L
            col = jnp.sum(e, axis=0, keepdims=True)
            dx = dx + _dot(m, dyk, _TN)                  # the other lanes: 0
            ddtr = jnp.where(head_row == j, col, ddtr)
            dgr = jnp.where(head_row == j, -col * dtj, dgr)
            dwk = jnp.sum(mine(gw), axis=1, keepdims=True)
            wj = w[:, j:j + 1]
            dgl = jnp.sum(dwk * wj, axis=0, keepdims=True) + dec[:, j:j + 1] \
                * jnp.sum(jnp.sum(jnp.where(row == k, gs, 0.0), axis=1,
                                  keepdims=True), axis=0, keepdims=True)
            dgj = (jnp.sum(e * dtj, axis=1, keepdims=True)
                   + jnp.sum(mine(gy), axis=1, keepdims=True) - dwk * wj
                   + jnp.where(last, dgl, 0.0))
            dgc = jnp.where(head == j, dgj, dgc)
            ddtc = jnp.where(head == j, dwk * egl[:, j:j + 1], ddtc)
        dx_ref[0, :, sl] = dx.astype(mm)
    dgr_ref[0, 0] = dgr + jnp.transpose(dgc)[:hb]
    ddtr_ref[0, 0] = ddtr + jnp.transpose(ddtc)[:hb]
    dcb_scr[...] += dcb
    db_scr[...] += db
    dc_scr[...] += dc

    @pl.when(h % blocks == blocks - 1)
    def _group_out():
        dcbm = dcb_scr[...].astype(mm)
        dc_ref[0] = (dc_scr[...] + _dot(dcbm, bm)).astype(mm)
        db_ref[0] = (db_scr[...] + _dot(dcbm, cm, _TN)).astype(mm)

    @pl.when(n == pl.num_programs(1) - 1)
    def _flush():
        ds0_ref[0] = ds_scr[h]


def _call(kernel, rev, operands, ins, outs, C, scratch):
    """The pallas_call both kernels make, under the `ssd.core` scope (the
    backward rule is traced outside the mixer's; chipbench/metrics/_stack.py
    finds the core's device time by it). `operands` start with x, B, C, G,
    dt, D and a state; `ins` are their `specs` keys, `outs` (key, dtype) of
    each result."""
    x, bm, gr, s0 = operands[0], operands[1], operands[3], operands[6]
    Bz, S, HP = x.shape
    (nH, hb), N, nC = gr.shape[1:3], s0.shape[-1], S // C
    P = HP // (nH * hb)
    W, GN = max(P, 128), bm.shape[-1]
    blocks = nH // (GN // N)                  # head blocks of a group
    nn = (lambda n: nC - 1 - n) if rev else (lambda n: n)
    # A state is touched at the sweep's ends only: elsewhere its block stays
    # where it is, and nothing is fetched or written back for it.
    at = lambda edge: lambda b, n, h: (b, jnp.where(n == edge, h, 0), 0)
    specs = dict(
        x=pl.BlockSpec((1, C, hb * P), lambda b, n, h: (b, nn(n), h)),
        bc=pl.BlockSpec((1, C, N), lambda b, n, h: (b, nn(n), h // blocks)),
        row=pl.BlockSpec((1, 1, hb, C), lambda b, n, h: (b, h, 0, nn(n))),
        d=pl.BlockSpec((1, hb * P), lambda b, n, h: (0, h)),
        s_in=pl.BlockSpec((1, hb * P, N), at(0)),
        s_out=pl.BlockSpec((1, hb * P, N), at(nC - 1)),
        states=pl.BlockSpec((1, 1, hb * P, N),
                            lambda b, n, h: (b, nn(n), h, 0)),
        dd=pl.BlockSpec((1, 1, 1, hb * P), lambda b, n, h: (b, nn(n), 0, h)))
    shape = dict(x=x.shape, bc=bm.shape, row=gr.shape, s_out=s0.shape,
                 states=(Bz, nC, HP, N), dd=(Bz, nC, 1, HP))
    with jax.named_scope("ssd.core"):
        return pl.pallas_call(
            functools.partial(kernel, P=P, W=W, blocks=blocks),
            grid=(Bz, nC, nH),
            in_specs=[specs[i] for i in ins],
            out_specs=[specs[o] for o, _ in outs],
            out_shape=[jax.ShapeDtypeStruct(shape[o], dt) for o, dt in outs],
            scratch_shapes=[pltpu.VMEM((nH, hb * P, N), _F32),
                            pltpu.VMEM((C, C), _F32),
                            pltpu.VMEM((128, C), _F32), *scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=dispatch.interpret(),
        )(*operands)


_INS = ("x", "bc", "bc", "row", "row", "d", "s_in")


# Both calls are jitted on their own: a stack traces the mixer once a segment
# and a program (the step, the check), and each trace of a kernel's body (a
# few hundred operations a head, unrolled) is paid in the chip's worker, which
# traces slowly (PERF.md section 7). Under `jax.jit` the second and later call
# sites find the first one's jaxpr, and a program lowers it once.
@functools.partial(jax.jit, static_argnames=("C",))
def _ssd_fwd_call(x, bm, cm, gr, dtr, dw, s0, C):
    """x [B,S,H*P]; bm, cm [B,S,G*N]; gr, dtr [B,H/hb,hb,S] float32; dw
    [1,H*P] (D a lane); s0 [B,H*P,N] -> y, the final state, the chunk-start
    states [B,S/C,H*P,N] in x's dtype."""
    return _call(_fwd_kernel, False, (x, bm, cm, gr, dtr, dw, s0), ins=_INS,
                 outs=(("x", x.dtype), ("s_out", _F32), ("states", x.dtype)),
                 C=C, scratch=())


@functools.partial(jax.jit, static_argnames=("C",))
def _ssd_bwd_call(x, bm, cm, gr, dtr, dw, states, dy, dsf, C):
    N = dsf.shape[-1]
    *grads, dd, ds0 = _call(
        _bwd_kernel, True, (x, bm, cm, gr, dtr, dw, dsf, states, dy),
        ins=_INS + ("states", "x"),
        outs=(("x", x.dtype), ("bc", bm.dtype), ("bc", cm.dtype),
              ("row", _F32), ("row", _F32), ("dd", _F32), ("s_out", _F32)),
        C=C, scratch=[pltpu.VMEM((C, C), _F32), pltpu.VMEM((C, N), _F32),
                      pltpu.VMEM((C, N), _F32)])
    with jax.named_scope("ssd.core"):
        return (*grads, jnp.sum(dd, axis=(0, 1)), ds0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _ssd_kernels(x, bm, cm, gr, dtr, dw, s0, C):
    y, sf, _ = _ssd_fwd_call(x, bm, cm, gr, dtr, dw, s0, C)
    return y, sf


def _vjp_fwd(x, bm, cm, gr, dtr, dw, s0, C):
    y, sf, states = _ssd_fwd_call(x, bm, cm, gr, dtr, dw, s0, C)
    y, states = (checkpoint_name(a, n) for a, n in zip((y, states),
                                                       RESIDUAL_NAMES))
    return (y, sf), (x, bm, cm, gr, dtr, dw, states)


def _vjp_bwd(C, res, cts):
    dy, dsf = cts
    return _ssd_bwd_call(*res, dy, dsf.astype(_F32), C)


_ssd_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def ssd_chunked_pallas(x, dt, A, B, C, D, *, chunk: int = 256,
                       initial_state: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """`ssd_chunked_xla` as two Pallas kernels under a custom VJP (module
    docstring). The dispatcher `ssd_chunked` comes here on the TPU; tests
    come here directly and run the kernels in interpret mode. Everything
    around the two calls (G, the tokens of G and dt onto the lanes, D a lane,
    and their transposes in the backward) is under the `ssd.core` scope too."""
    Bz, S, H, P = x.shape
    G, N = B.shape[-2:]
    mm, hb = x.dtype, heads_per_step(H // G, P)
    assert (H // G) % hb == 0 and (hb * P) % max(P, 128) == 0, (H, G, P)
    with jax.named_scope("ssd.core"):
        x, dt, B, C = _pad_to_chunks(x, dt, B, C, chunk)
        Sp = x.shape[1]
        # [B,S,H] -> [B,H,chunks,chunk]; G a product with a triangle of ones.
        dt = jnp.swapaxes(dt.astype(_F32), 1, 2).reshape(Bz, H, -1, chunk)
        g = jnp.einsum("bhnj,ji->bhni", dt * A.astype(_F32)[:, None, None],
                       jnp.triu(jnp.ones((chunk, chunk), _F32)),
                       precision=_HI)
        rows = lambda a: a.reshape(Bz, H // hb, hb, Sp)
        s0 = (jnp.zeros((Bz, H * P, N), _F32) if initial_state is None
              else initial_state.astype(_F32).reshape(Bz, H * P, N))
        y, s = _ssd_kernels(
            x.reshape(Bz, Sp, H * P), B.astype(mm).reshape(Bz, Sp, G * N),
            C.astype(mm).reshape(Bz, Sp, G * N), rows(g), rows(dt),
            jnp.repeat(D.astype(_F32), P)[None], s0, chunk)
        return y.reshape(Bz, Sp, H, P)[:, :S], s.reshape(Bz, H, P, N)


# ---------------------------------------------------------------- dispatch


def use_kernels(platform: str, P: int, N: int, chunk: int, heads: int,
                groups: int, on_mesh: bool) -> bool:
    """The dispatch rule, a pure function of what the code observes: the
    kernels where a Mosaic call can run (`dispatch.mosaic`), the chunk and
    the state whole 128-lane tiles, heads that fill 128-lane groups inside
    their group of B and C, and every head's state within the VMEM scratch
    the kernels carry it in."""
    hp = max(1, 128 // P)
    return (dispatch.mosaic(platform, on_mesh)
            and dispatch.whole(chunk, N, hp * P) and heads % groups == 0
            and (heads // groups) % hp == 0
            and heads * P * N * 4 <= _STATE_BYTES)


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = 256,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD, same arguments and results as `ssd_recurrent`, any
    sequence length: the Pallas kernels where `use_kernels` says so, else
    `ssd_chunked_xla`. Each traced call counts once in the phase table, as
    `ssd.core.pallas` or `ssd.core.xla`, with its chunk, chunks, heads and
    state as attributes (layers under one scan trace once)."""
    (_, S, H, P), (G, N) = x.shape, B.shape[-2:]
    s = dispatch.site()
    kernels = use_kernels(s.platform, P, N, chunk, H, G, s.on_mesh)
    attrs = dict(chunk=chunk, chunks=-(-S // chunk), heads=H, state=N)
    if kernels:
        attrs["heads_per_step"] = heads_per_step(H // G, P)
    dispatch.observe("ssd.core", kernels, **attrs)
    body = ssd_chunked_pallas if kernels else ssd_chunked_xla
    return body(x, dt, A, B, C, D, chunk=chunk, initial_state=initial_state)
