"""The gated delta rule, both ways it is published: Kimi Delta Attention
(KDA), whose decay is one number a key channel, and Gated DeltaNet, whose
decay is one number a head and whose value heads share key heads.

Per value head, with a state S [d_k, d_v] in place of keys and values:

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale,        a_t = exp(g_t), g_t <= 0

One core serves both, told apart by shapes alone (no flag): `g` of rank 4
[B,S,H,d_k] is a decay a channel, of rank 3 [B,S,H] the same number in every
channel of a head; `q` and `k` with H_k heads where `v` has H_v = r H_k serve
r value heads each (key head i the value heads r i .. r i + r - 1). Which
body runs where: `kda_recurrent` takes both natively (the tests' oracle);
`kda_chunked_xla` is the per-channel rule's and takes a scalar-decay or
shared-key call as a broadcast (`_per_channel`: g over the channels, q and k
repeated over their value heads, so autodiff sums dg over the channels and
dq, dk over the value heads); the Pallas path has a forward and a backward
kernel for each rule, picked by g's rank. What the scalar-decay kernels skip:
e^(G_t - G_s) is ONE masked [C, C] float32 matrix D a value head, every entry
at most 1, so there are no sub-blocks, no references and no cap; K K^T and
Q K^T are one product each a KEY head (a grid step holds two key heads and
the r value heads each serves, q and k fetched once), A = beta (K K^T . D) and
P = Q K^T . D elementwise a value head; e^G and e^(G_C - G) are [C, 1]
columns that scale rows; g is read and dg written [B,S,H] as beta and dbeta
are, dq and dk are summed over a key head's value heads inside the kernel,
and dG is the row sums less the column sums of one float32 matrix dA . A +
dP . P, so a pair's term enters dg at its two tokens with the same rounding
(the per-channel backward writes it through two bfloat16 products). The
inverse, W, U and the state's recurrence are the same functions in both
(chipbench/reduce/qwen3_next_counts.py counts the scalar rule). A per-channel
call with H_k = H_v traces what it traced; one with shared key heads (no
configuration has it) keeps `_per_channel`'s repeat.

`kda_recurrent` is that recurrence, one token at a time (tests, and the shape
a decode step will take). The training path runs the chunked form: the
sequence is cut into chunks of `chunk` tokens; inside a chunk the rank-one
updates are folded into one triangular system (the WY / UT transform of the
delta rule); between chunks the state S is carried. With G_t the decay summed
in log space from the chunk's start (float32), u_t = beta_t (v_t - (Diag(a_t)
S_{t-1})^T k_t):

    (I + A) U = beta (V - (K e^G) S_0),   A_ts = beta_t sum_c k_tc k_sc e^(G_tc - G_sc), s < t
    O = (Q e^G) S_0 + tril(Q K^T e^(G_t - G_s)) U
    S_C = Diag(e^G_C) S_0 + (K e^(G_C - G))^T U

e^(G_t - G_s) is never formed per pair of tokens ([C, C, d_k]) and never as
e^G_t * e^-G_s from the chunk's start either (the second factor overflows
float32 once a chunk decays by more than e^88): rows are taken in sub-blocks
of `sub` tokens, each against its own reference r_i (the decay at the
sub-block's start), so that both factors are at most 1 for every earlier
sub-block and at most e^(sub * max|g|) inside the sub-block itself (capped
at e^80: exact while a channel decays by less than that in `sub` steps).

Precision, the same in both implementations: the matmuls take operands in
the inputs' dtype (bfloat16 on the chip) and accumulate in float32; g, G, the
decay factors, A, the triangular inverse and the state are float32, and u0
goes into the state update in float32.

`kda_chunked` is the entry point and dispatches on what it observes
(`use_kernels`, no knob):

- **`kda_chunked_pallas`**: two Pallas (Mosaic) kernels under a
  `jax.custom_vjp`, on a TPU when d_k, d_v and the chunk are multiples of
  128 and no multi-device mesh is active. What follows is the per-channel
  pair; the scalar-decay pair differs as said above and shares the grid's
  shape, the residuals and their names. Grid (batch, head pair, chunk),
  the chunk axis sequential. q, k, v, g are read where they lie: [B, S, H, d] is
  [B, S, H*d] and a head is a d-lane column block of it (no [B,H,S,d]
  copies). A grid step holds one chunk of two neighbouring heads (one if the
  head count is odd), each worked on its own, in VMEM: G (a product
  with a triangle of ones), the sub-blocks' references and bounded factors,
  A and P, T = (I + A)^-1 (`_inv_unit_lower`'s algorithm on one tile: forward
  substitution on the diagonal 16-blocks, all eight at once on the vector
  unit, then block merges as float32 MXU products), W, U, the output rows;
  the state [d_k, d_v] float32 lives in VMEM scratch across the chunk axis.
  The forward writes o, the final state and, for the backward, the
  chunk-start states [B,H,N,d_k,d_v] float32 and T [B,H,N,C,C] in the
  compute type. The backward kernel walks the chunks in reverse with the
  state's cotangent in scratch, recomputes a chunk's factors from q, k, v,
  g, beta, reads the saved state and T, and writes dq, dk, dv, dg, dbeta:
  every [C, C] and [C, d] product is on the MXU, and the inverse is not
  differentiated through its construction: dA = -(T^T dW) W^T - (T^T dU0)
  U0^T, strictly lower. `RESIDUAL_NAMES` names o, the states and T
  (`checkpoint_name` in the forward rule) so that a remat policy keeps them
  and the forward kernel runs once (`models/transformer.py` `layer_scan_body`).
- **`kda_chunked_xla`**: the same algorithm in plain XLA, differentiable by
  autodiff: the fallback on the CPU, for narrow heads or chunks, and under a
  mesh (a Mosaic call there needs `shard_map`; no configuration trains a KDA
  stack on a mesh yet), and the kernels' oracle in the tests beside
  `kda_recurrent`.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_CAP = 80.0  # exponent cap inside a sub-block, see the module docstring


def short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Causal depthwise convolution along the sequence: x [B, S, ...ch],
    w [K, ...ch] -> y_t = sum_j w[j] x_{t-K+1+j} (w[K-1] meets x_t)."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (K - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    return sum(xp[:, j:j + S] * w[j].astype(x.dtype) for j in range(K))


def l2_normalize(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(_F32)
    return (xf * jax.lax.rsqrt(jnp.sum(xf * xf, -1, keepdims=True) + eps)
            ).astype(x.dtype)


def _per_channel(q, k, v, g):
    """(q, k, g) as the per-channel rule takes them: q and k repeated over
    the value heads that share them (H_v = r H_k: key head i serves value
    heads r i .. r i + r - 1), a decay a head [B,S,H] broadcast over the key
    channels. A call that is per-channel already comes back as it is:
    nothing is traced for it."""
    r = v.shape[2] // q.shape[2]
    if r > 1:
        q, k = jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2)
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], g.shape + q.shape[-1:])
    return q, k, g


def kda_recurrent(q, k, v, g, beta, *, scale: Optional[float] = None,
                  initial_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Token by token. q, k [B,S,Hk,dk]; v [B,S,H,dv], H = r Hk; g the log
    decay, [B,S,H,dk] (a channel) or [B,S,H] (a head); beta [B,S,H] ->
    (o [B,S,H,dv] in v's dtype, S [B,H,dk,dv] f32). The scalar decay and
    the shared key heads are taken as they come, not through
    `_per_channel`: the chunked bodies' oracle owes them nothing."""
    B, S, H = beta.shape
    dk, dv = q.shape[-1], v.shape[-1]
    r = H // q.shape[2]
    if r > 1:  # value head j reads key head j // r
        of = jnp.arange(H) // r
        q, k = q[:, :, of], k[:, :, of]
    if g.ndim == 3:
        g = g[..., None]  # exp(g) meets every channel of the state's rows
    scale = dk ** -0.5 if scale is None else scale
    s0 = (jnp.zeros((B, H, dk, dv), _F32) if initial_state is None
          else initial_state.astype(_F32))

    def step(s, x):
        qt, kt, vt, gt, bt = x                       # [B,H,*]
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=_HI))
        s = s + kt[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=_HI)

    xs = tuple(jnp.moveaxis(a.astype(_F32), 1, 0) for a in (q, k, v, g, beta))
    s, o = jax.lax.scan(step, s0, xs)
    return (jnp.moveaxis(o, 0, 1) * scale).astype(v.dtype), s


def _inv_unit_lower(a: jax.Array, base: int = 16) -> jax.Array:
    """(I + a)^-1 for strictly lower-triangular a [..., C, C], float32, C =
    base * 2^j: forward substitution on the `base`-wide diagonal blocks (all
    at once), then block merges [[T1, 0], [-T2 a21 T1, T2]] up to C."""
    C = a.shape[-1]
    base = min(base, C)
    nb = C // base
    assert nb * base == C and nb & (nb - 1) == 0, (C, base)
    diag = jnp.stack([a[..., i * base:(i + 1) * base, i * base:(i + 1) * base]
                      for i in range(nb)], axis=-3)   # [..., nb, base, base]
    eye = jnp.eye(base, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (base,))]
    for i in range(1, base):
        prev = jnp.stack(rows, axis=-2)               # [..., i, base]
        rows.append(eye[i] - jnp.einsum("...j,...jc->...c",
                                        diag[..., i, :i], prev,
                                        precision=_HI))
    t = jnp.stack(rows, axis=-2)                      # [..., nb, base, base]
    m = base
    while m < C:
        t1, t2 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        a21 = jnp.stack(
            [a[..., (2 * j + 1) * m:(2 * j + 2) * m, 2 * j * m:(2 * j + 1) * m]
             for j in range(C // (2 * m))], axis=-3)
        t21 = -jnp.matmul(jnp.matmul(t2, a21, precision=_HI), t1,
                          precision=_HI)
        t = jnp.concatenate(
            [jnp.concatenate([t1, jnp.zeros_like(t1)], -1),
             jnp.concatenate([t21, t2], -1)], -2)
        m *= 2
    return t[..., 0, :, :]


def _pad_to_chunks(q, k, v, g, beta, C):
    """The sequence padded at its end to a multiple of C with tokens that
    leave the state as it is (k = v = beta = g = 0)."""
    pad = (-q.shape[1]) % C
    if not pad:
        return q, k, v, g, beta
    return tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                 for a in (q, k, v, g, beta))


def kda_chunked_xla(q, k, v, g, beta, *, chunk: int = 128, sub: int = 32,
                    scale: Optional[float] = None,
                    initial_state: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """Chunked KDA in plain XLA, differentiable by autodiff: the fallback of
    `kda_chunked` and the kernels' oracle. Same arguments and results as
    `kda_recurrent`. `chunk` is 16 * 2^j; a sequence that is not a multiple
    of it is padded at its end with tokens that leave the state as it is
    (k = v = beta = g = 0)."""
    q, k, g = _per_channel(q, k, v, g)
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    C = chunk
    sub = min(sub, C)
    q, k, v, g, beta = _pad_to_chunks(q, k, v, g, beta, C)
    N, n = q.shape[1] // C, C // sub
    mm = q.dtype

    def chunks(a):  # [B,S,H,x] -> [B,H,N,C,x]
        return jnp.moveaxis(a, 1, 2).reshape(B, H, N, C, a.shape[-1])

    q, k, v = chunks(q), chunks(k), chunks(v)
    g = chunks(g.astype(_F32))
    beta = chunks(beta[..., None].astype(_F32))
    G = jnp.cumsum(g, axis=3)                          # from the chunk's start
    kf, qf = k.astype(_F32), q.astype(_F32)

    # Inside the chunk: rows by sub-block i against reference r_i.
    def subs(a):  # [B,H,N,C,x] -> [B,H,N,n,sub,x]
        return a.reshape(B, H, N, n, sub, a.shape[-1])

    Gs = subs(G)
    r = Gs[..., :1, :] - subs(g)[..., :1, :]           # [B,H,N,n,1,dk]
    row = jnp.exp(Gs - r)                              # <= 1
    j_of_s = jnp.arange(C) // sub
    keep = (j_of_s[None, :] <= jnp.arange(n)[:, None])[..., None]  # [n,C,1]
    col = jnp.exp(jnp.where(
        keep, jnp.minimum(r - G[:, :, :, None], _CAP), -jnp.inf))
    kh = (kf[:, :, :, None] * col).astype(mm)          # [B,H,N,n,C,dk]
    lhs = jnp.concatenate([subs(kf * beta) * row, subs(qf) * row],
                          axis=-2).astype(mm)          # [B,H,N,n,2*sub,dk]
    ap = jnp.einsum("bhnitd,bhnisd->bhnits", lhs, kh,
                    preferred_element_type=_F32)
    t_idx = jnp.arange(C)
    a = jnp.where(t_idx[:, None] > t_idx[None, :],
                  ap[..., :sub, :].reshape(B, H, N, C, C), 0.0)
    p = jnp.where(t_idx[:, None] >= t_idx[None, :],
                  ap[..., sub:, :].reshape(B, H, N, C, C), 0.0).astype(mm)
    T = _inv_unit_lower(a).astype(mm)                  # (I + A)^-1

    eG = jnp.exp(G)
    rhs = jnp.concatenate([kf * eG * beta, v.astype(_F32) * beta],
                          axis=-1).astype(mm)
    wu = jnp.einsum("bhnts,bhnsx->bhntx", T, rhs, preferred_element_type=_F32)
    w, u0 = wu[..., :dk].astype(mm), wu[..., dk:]      # u0 stays float32
    qt = (qf * eG).astype(mm)
    Gl = G[..., -1:, :]
    kbar = (kf * jnp.exp(Gl - G)).astype(mm)
    decay = jnp.exp(Gl[..., 0, :])                     # [B,H,N,dk]

    def step(s, x):
        w_n, u0_n, qt_n, kbar_n, decay_n = x
        sm = s.astype(mm)
        u = u0_n - jnp.einsum("bhtk,bhkv->bhtv", w_n, sm,
                              preferred_element_type=_F32)
        o = jnp.einsum("bhtk,bhkv->bhtv", qt_n, sm,
                       preferred_element_type=_F32)
        s = s * decay_n[..., None] + jnp.einsum(
            "bhtk,bhtv->bhkv", kbar_n, u.astype(mm),
            preferred_element_type=_F32)
        return s, (u.astype(mm), o)

    s0 = (jnp.zeros((B, H, dk, dv), _F32) if initial_state is None
          else initial_state.astype(_F32))
    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u0, qt, kbar, decay))
    s, (u, o_inter) = jax.lax.scan(step, s0, xs)
    u, o_inter = jnp.moveaxis(u, 0, 2), jnp.moveaxis(o_inter, 0, 2)
    o = o_inter + jnp.einsum("bhnts,bhnsv->bhntv", p, u,
                             preferred_element_type=_F32)
    o = jnp.moveaxis(o.reshape(B, H, N * C, dv), 1, 2)[:, :S]
    return (o * scale).astype(v.dtype), s


# ------------------------------------------------------------ Pallas kernels
#
# One grid step is one chunk of one head: everything between the chunk's
# inputs and its outputs lives in VMEM ([C, C] and [C, d] tiles of 64 KB).

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))

# What `_vjp_fwd` keeps for the backward kernel besides its own inputs, named
# so that a remat policy can keep them (`models/transformer.py` `layer_scan_body`): o
# because the layer's recomputation needs it, the chunk-start states and the
# chunks' inverses because the backward kernel reads them. A pallas_call is
# not a dot: under a dots-only policy the forward kernel would run twice.
RESIDUAL_NAMES = ("kda_o", "kda_states", "kda_tinv")


def _dot(a, b, dims=_NN, exact=False):
    """MXU product with float32 accumulation; `exact` asks for float32
    operands at full precision (the cumulative sums and the inverse)."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               precision=_HI if exact else None,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _head_col(blk, h):
    """Column h of a [C, H] block as [C, 1] (beta lies [B, S, H])."""
    return jnp.sum(jnp.where(_iota(blk.shape, 1) == h, blk, 0.0), axis=1,
                   keepdims=True)


def _flip(x):
    """[1, n] -> [n, 1] or [n, 1] -> [1, n]: broadcast to a square tile and
    transposed on the XLU."""
    n = max(x.shape)
    t = jnp.transpose(jnp.broadcast_to(x, (n, n)))
    return t[:, :1] if x.shape[0] == 1 else t[:1]


def _rows(x):
    """Row sums [C, 1]."""
    return jnp.sum(x, axis=1, keepdims=True)


def _total(x):
    """The sum of a tile [1, 1]: down the sublanes first."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _tree_sum(xs):
    while len(xs) > 1:
        xs = [sum(xs[i:i + 2]) for i in range(0, len(xs), 2)]
    return xs[0]


def _inv_unit_lower_vmem(a, scr, base: int = 16):
    """(I + a)^-1 for strictly lower-triangular a [C, C] float32, the
    algorithm of `_inv_unit_lower` on one tile: forward substitution on the
    `base`-wide diagonal blocks, all blocks at once, then block merges
    [[T1, 0], [-T2 a21 T1, T2]] up to C as MXU products at full float32
    precision. `scr` is a [C, C] float32 VMEM scratch.

    Substitution: each block's rows are rotated so that its diagonal block
    lies in lanes 0..base-1; row i of every block is then one strided load
    [C/base, C], and row i of the inverse is e_i - sum_j a_ij row_j with
    a_ij a lane of that load, broadcast."""
    C = a.shape[0]
    base = min(base, C)
    nb = C // base
    for b in range(nb):
        blk = a[b * base:(b + 1) * base]
        scr[b * base:(b + 1) * base, :] = (
            pltpu.roll(blk, C - b * base, 1) if b else blk)
    lane = _iota((nb, C), 1)
    rows = []
    for i in range(base):
        x = jnp.where(lane == i, 1.0, 0.0)
        if i:
            d = scr[pl.ds(i, nb, stride=base), :]
            x = x - _tree_sum([d[:, j:j + 1] * rows[j] for j in range(i)])
        rows.append(x)
    for i in range(base):
        scr[pl.ds(i, nb, stride=base), :] = rows[i]
    blocks = [scr[b * base:(b + 1) * base, :] for b in range(nb)]
    blocks = [pltpu.roll(x, b * base, 1) if b else x
              for b, x in enumerate(blocks)]       # base rows each, in place
    t, s = _iota((C, C), 0), _iota((C, C), 1)
    m = base
    while m < C:
        # Pairs of m-blocks: only the odd blocks' rows change.
        x = jnp.concatenate(blocks, 0)
        off = jnp.where(((t ^ s) < 2 * m) & ((t & m) != 0) & ((s & m) == 0),
                        a, 0.0)
        odd = jnp.concatenate(blocks[1::2], 0)     # [C/2, C]
        odd = odd - _dot(_dot(odd, off, exact=True), x, exact=True)
        h = odd.shape[0] // (len(blocks) // 2)
        blocks = [jnp.concatenate(
            [blocks[2 * p], odd[p * h:(p + 1) * h]], 0)
            for p in range(len(blocks) // 2)]
        m *= 2
    return blocks[0]


def _chunk_terms(q, k, v, g, beta, sub, with_a):
    """A chunk's quantities that need no state, as the module docstring and
    `kda_chunked_xla` define them: q, k [C, dk], v [C, dv] in the compute
    type, g [C, dk] and beta [C, 1] float32."""
    C, dk = q.shape
    mm = q.dtype
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    t, s = _iota((C, C), 0), _iota((C, C), 1)
    G = _dot((s <= t).astype(_F32), g, exact=True)       # running log-decay
    # R_t: the decay at the start of t's sub-block, the reference of row t.
    E = G - g
    refs = [E[i * sub:i * sub + 1] for i in range(C // sub)]
    R = jnp.concatenate([jnp.broadcast_to(r, (sub, dk)) for r in refs], 0)
    row = jnp.exp(G - R)                                 # <= 1
    lhs_a, lhs_p = kf * beta * row, qf * row
    rows = _iota((C, 1), 0)
    kh, cols, live, a, p = [], [], [], [], []
    for i, r in enumerate(refs):
        d = r - G                                        # r_i - G_s
        keep = rows < (i + 1) * sub
        cols.append(jnp.where(keep, jnp.exp(jnp.minimum(d, _CAP)), 0.0))
        kh.append((kf * cols[i]).astype(mm))
        live.append(keep & (d < _CAP))
        sl = slice(i * sub, (i + 1) * sub)
        if with_a:
            a.append(_dot(lhs_a[sl].astype(mm), kh[i], _NT))
        p.append(_dot(lhs_p[sl].astype(mm), kh[i], _NT))
    eG = jnp.exp(G)
    Gl = jnp.sum(jnp.where(rows == C - 1, G, 0.0), axis=0, keepdims=True)
    ekb = jnp.exp(Gl - G)
    x = dict(qf=qf, kf=kf, vf=vf, beta=beta, row=row, eG=eG, ekb=ekb,
             lhs_a=lhs_a, lhs_p=lhs_p, kh=kh, cols=cols, live=live,
             p=jnp.where(t >= s, jnp.concatenate(p, 0), 0.0).astype(mm),
             rhs_w=(kf * beta * eG).astype(mm), rhs_u=(vf * beta).astype(mm),
             qt=(qf * eG).astype(mm), kbar=(kf * ekb).astype(mm),
             decay=jnp.exp(Gl))                          # [1, dk]
    if with_a:
        x["a"] = jnp.where(t > s, jnp.concatenate(a, 0), 0.0)
    return x


def _chunk_solve(x, tb, s):
    """W, U0 and U of a chunk from T = (I + A)^-1 (compute type) and the
    chunk-start state s (float32): u0 stays float32 into the state update."""
    mm = tb.dtype
    w = _dot(tb, x["rhs_w"]).astype(mm)
    u0 = _dot(tb, x["rhs_u"])
    sm = s.astype(mm)
    u = u0 - _dot(w, sm)
    return w, u0, u.astype(mm), sm


def _fwd_chunk(q, k, v, g, beta, s, scale, sub, inv_scr):
    """One chunk of one head -> (o, T, the next state)."""
    x = _chunk_terms(q, k, v, g, beta, sub, with_a=True)
    tb = _inv_unit_lower_vmem(x["a"], inv_scr).astype(q.dtype)
    w, u0, ub, sm = _chunk_solve(x, tb, s)
    o = (_dot(x["qt"], sm) + _dot(x["p"], ub)) * scale
    return o, tb, s * _flip(x["decay"]) + _dot(x["kbar"], ub, _TN)


def _bwd_chunk(q, k, v, g, beta, s, tb, do, ds1, scale, sub):
    """One chunk of one head in the reverse sweep: s the chunk-start state,
    tb its inverse, ds1 the cotangent of its end state -> dq, dk, dv, dg,
    dbeta [C, 1] and the cotangent of the start state. The casts to the
    compute type pass cotangents through as autodiff's `astype` does."""
    mm = q.dtype
    x = _chunk_terms(q, k, v, g, beta, sub, with_a=False)
    C = q.shape[0]
    qf, kf, vf = x["qf"], x["kf"], x["vf"]
    w, u0, ub, sm = _chunk_solve(x, tb, s)
    ds1b = ds1.astype(mm)
    do = (do.astype(_F32) * scale).astype(mm)
    t, s_i = _iota((C, C), 0), _iota((C, C), 1)
    # o = qt S + P U;  S' = decay S + kbar^T U;  U = U0 - W S.
    dqt = _dot(do, sm, _NT)
    dp = jnp.where(t >= s_i, _dot(do, ub, _NT), 0.0).astype(mm)
    dub = (_dot(x["p"], do, _TN) + _dot(x["kbar"], ds1b)).astype(mm)
    dkbar = _dot(ub, ds1b, _NT)
    dw = (-_dot(dub, sm, _NT)).astype(mm)
    ds0 = (ds1 * _flip(x["decay"]) + _dot(x["qt"], do, _TN)
           - _dot(w, dub, _TN))
    ones = jnp.ones((8, s.shape[1]), _F32)
    ddecay = _dot(ones, ds1 * s, _NT, exact=True)[:1]    # [1, dk]
    # [W, U0] = T [rhs_w, rhs_u], and dA = -T^T dT T^T with dT = dW rhs_w^T +
    # dU0 rhs_u^T is -(drhs_w W^T + drhs_u U0^T): T's own construction is
    # not differentiated through.
    drhs_w, drhs_u = _dot(tb, dw, _TN), _dot(tb, dub, _TN)
    da = jnp.where(t > s_i, -(_dot(drhs_w.astype(mm), w, _NT)
                              + _dot(drhs_u.astype(mm), u0.astype(mm), _NT)),
                   0.0).astype(mm)
    # A and P, a sub-block of rows at a time against its reference.
    dlhs_a, dlhs_p, dk, dG, dref = [], [], 0.0, 0.0, []
    for i, kh in enumerate(x["kh"]):
        sl = slice(i * sub, (i + 1) * sub)
        dlhs_a.append(_dot(da[sl], kh))
        dlhs_p.append(_dot(dp[sl], kh))
        dkh = (_dot(da[sl], x["lhs_a"][sl].astype(mm), _TN)
               + _dot(dp[sl], x["lhs_p"][sl].astype(mm), _TN))
        dk = dk + dkh * x["cols"][i]
        e = jnp.where(x["live"][i], dkh * kf * x["cols"][i], 0.0)
        dG = dG - e
        dref.append(jnp.sum(e, axis=0, keepdims=True))
    dlhs_a, dlhs_p = jnp.concatenate(dlhs_a, 0), jnp.concatenate(dlhs_p, 0)
    f = dlhs_a * x["lhs_a"] + dlhs_p * x["lhs_p"]        # d(G - R) of row
    h = dkbar * kf * x["ekb"]
    dG = (dG + f + drhs_w * (kf * x["beta"] * x["eG"])
          + dqt * (qf * x["eG"]) - h)
    # The reference of sub-block i is row i*sub of E = G - g.
    rows = _iota((C, 1), 0)
    dE = sum(jnp.where(rows == i * sub, r - jnp.sum(
        f[i * sub:(i + 1) * sub], axis=0, keepdims=True), 0.0)
             for i, r in enumerate(dref))
    dGl = jnp.sum(h, axis=0, keepdims=True) + ddecay * x["decay"]
    dq = dlhs_p * x["row"] + dqt * x["eG"]
    dk = (dk + dlhs_a * (x["beta"] * x["row"])
          + drhs_w * (x["beta"] * x["eG"]) + dkbar * x["ekb"])
    dbeta = jnp.sum(dlhs_a * (kf * x["row"]) + drhs_w * (kf * x["eG"]),
                    axis=1, keepdims=True) + jnp.sum(
                        drhs_u * vf, axis=1, keepdims=True)
    # G = cumsum(g), Gl the whole chunk's sum.
    dg = (_dot((s_i >= t).astype(_F32), dG + dE, exact=True) - dE + dGl)
    return dq, dk, drhs_u * x["beta"], dg, dbeta, ds0


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                o_ref, sf_ref, st_ref, t_ref, s_scr, inv_scr, *, scale, sub,
                hb):
    """A grid step: one chunk of `hb` neighbouring heads, each on its own
    (independent chains of products, which the scheduler interleaves)."""
    n = pl.program_id(2)
    dk, dv = s_scr.shape[1:]

    @pl.when(n == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    for j in range(hb):
        kl, vl = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        s = s_scr[j]
        o, tb, s_next = _fwd_chunk(
            q_ref[0, :, kl], k_ref[0, :, kl], v_ref[0, :, vl],
            g_ref[0, :, kl],
            _head_col(beta_ref[0], pl.program_id(1) * hb + j), s, scale, sub,
            inv_scr)
        o_ref[0, :, vl] = o.astype(o_ref.dtype)
        st_ref[0, j, 0] = s
        t_ref[0, j, 0] = tb
        s_scr[j] = s_next

    @pl.when(n == pl.num_programs(2) - 1)
    def _flush():
        sf_ref[0] = s_scr[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, t_ref, do_ref,
                dsf_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds0_ref,
                ds_scr, *, scale, sub, hb):
    """The reverse sweep's grid step: ds_scr holds the cotangent of the
    chunk's end state on entry and of its start state on exit."""
    n = pl.program_id(2)
    dk, dv = ds_scr.shape[1:]

    @pl.when(n == 0)
    def _init():
        ds_scr[...] = dsf_ref[0]

    for j in range(hb):
        kl, vl = slice(j * dk, (j + 1) * dk), slice(j * dv, (j + 1) * dv)
        dq, dk_, dv_, dg, dbeta, ds0 = _bwd_chunk(
            q_ref[0, :, kl], k_ref[0, :, kl], v_ref[0, :, vl],
            g_ref[0, :, kl],
            _head_col(beta_ref[0], pl.program_id(1) * hb + j),
            st_ref[0, j, 0], t_ref[0, j, 0], do_ref[0, :, vl], ds_scr[j],
            scale, sub)
        dq_ref[0, :, kl] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, kl] = dk_.astype(dk_ref.dtype)
        dv_ref[0, :, vl] = dv_.astype(dv_ref.dtype)
        dg_ref[0, :, kl] = dg
        db_ref[0, j] = _flip(dbeta)
        ds_scr[j] = ds0

    @pl.when(n == pl.num_programs(2) - 1)
    def _flush():
        ds0_ref[0] = ds_scr[...]


# --------------------------------------------- one decay a head, shared keys
#
# A grid step is one chunk of two neighbouring KEY heads (one if their count
# is odd) and the r value heads each serves: K K^T and Q K^T once a key head,
# everything that holds a decay once a value head.


def _scalar_terms(qf, kf, v, g, beta, kk, qk, mm):
    """A chunk's quantities that need no state at ONE decay a head: qf, kf
    [C, dk] float32 and kk = K K^T, qk = Q K^T [C, C] (the key head's, from
    operands in the compute type `mm`), v [C, dv] in `mm`, g and beta [C, 1]
    float32 (the value head's). e^(G_t - G_s) is the one masked [C, C]
    matrix D, every entry at most 1: no sub-block, no reference, no cap."""
    C = qf.shape[0]
    vf = v.astype(_F32)
    t, s = _iota((C, C), 0), _iota((C, C), 1)
    # The running log-decay: G_s on the lanes, then G_t in every lane of row t.
    Gr = jnp.sum(jnp.where(t <= s, g, 0.0), axis=0, keepdims=True)
    Gb = jnp.transpose(jnp.broadcast_to(Gr, (C, C)))
    G = Gb[:, :1]
    D = jnp.exp(jnp.where(t >= s, Gb - Gr, -jnp.inf))   # 0 above the diagonal
    Ds = jnp.where(t > s, D, 0.0)                       # strictly lower
    pf = qk * D
    eG, Gl = jnp.exp(G), Gr[:, C - 1:]
    ekb, be = jnp.exp(Gl - G), beta * eG
    return dict(kf=kf, qf=qf, vf=vf, beta=beta, kk=kk, D=D, Ds=Ds, pf=pf,
                eG=eG, ekb=ekb, be=be, a=kk * Ds * beta, p=pf.astype(mm),
                rhs_w=(kf * be).astype(mm), rhs_u=(vf * beta).astype(mm),
                qt=(qf * eG).astype(mm), kbar=(kf * ekb).astype(mm),
                decay=jnp.exp(Gl))                          # [1, 1]


def _scalar_key_head(q_ref, k_ref, kl):
    """(q, k, qf, kf, K K^T, Q K^T) of the key head in lanes `kl`."""
    q, k = q_ref[0, :, kl], k_ref[0, :, kl]
    return (q, k, q.astype(_F32), k.astype(_F32), _dot(k, k, _NT),
            _dot(q, k, _NT))


def _scalar_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                       o_ref, sf_ref, st_ref, t_ref, s_scr, inv_scr, *,
                       scale, hb):
    """`_fwd_kernel` at one decay a head: `hb` value heads, r of them to
    each key head of the q, k block, g read [C, H] as beta is."""
    n = pl.program_id(2)
    dk, dv = s_scr.shape[1:]
    r = hb * dk // q_ref.shape[2]

    @pl.when(n == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    for i in range(hb // r):
        q, k, qf, kf, kk, qk = _scalar_key_head(
            q_ref, k_ref, slice(i * dk, (i + 1) * dk))
        for j in range(i * r, (i + 1) * r):
            vl, h = slice(j * dv, (j + 1) * dv), pl.program_id(1) * hb + j
            s = s_scr[j]
            x = _scalar_terms(qf, kf, v_ref[0, :, vl],
                              _head_col(g_ref[0], h),
                              _head_col(beta_ref[0], h), kk, qk, q.dtype)
            tb = _inv_unit_lower_vmem(x["a"], inv_scr).astype(q.dtype)
            w, u0, ub, sm = _chunk_solve(x, tb, s)
            o = (_dot(x["qt"], sm) + _dot(x["p"], ub)) * scale
            o_ref[0, :, vl] = o.astype(o_ref.dtype)
            st_ref[0, j, 0] = s
            t_ref[0, j, 0] = tb
            s_scr[j] = s * x["decay"] + _dot(x["kbar"], ub, _TN)

    @pl.when(n == pl.num_programs(2) - 1)
    def _flush():
        sf_ref[0] = s_scr[...]


def _scalar_bwd_head(x, tb, s, do, ds1, scale):
    """One chunk of one value head in the reverse sweep, as `_bwd_chunk`:
    -> its terms of dq and dk [C, dk] float32, of dKK and dQK [C, C], dv,
    dg and dbeta as rows [1, C], and the cotangent of the start state. dA
    and dP stay float32: they meet D elementwise, and M = dA . A + dP . P is
    one float32 matrix whose row sums less column sums are dG, so a pair's
    term leaves dg between its two tokens exactly as it entered."""
    mm = tb.dtype
    C = tb.shape[0]
    kf, qf, beta = x["kf"], x["qf"], x["beta"]
    w, u0, ub, sm = _chunk_solve(x, tb, s)
    ds1b = ds1.astype(mm)
    do = (do.astype(_F32) * scale).astype(mm)
    t, s_i = _iota((C, C), 0), _iota((C, C), 1)
    # o = qt S + P U;  S' = decay S + kbar^T U;  U = U0 - W S.
    dqt = _dot(do, sm, _NT)
    dp = _dot(do, ub, _NT)
    dub = (_dot(x["p"], do, _TN) + _dot(x["kbar"], ds1b)).astype(mm)
    dkbar = _dot(ub, ds1b, _NT)
    dw = (-_dot(dub, sm, _NT)).astype(mm)
    ds0 = ds1 * x["decay"] + _dot(x["qt"], do, _TN) - _dot(w, dub, _TN)
    ddecay = _total(ds1 * s)
    # dA = -(drhs_w W^T + drhs_u U0^T), as in `_bwd_chunk`.
    drhs_w, drhs_u = _dot(tb, dw, _TN), _dot(tb, dub, _TN)
    da = -(_dot(drhs_w.astype(mm), w, _NT)
           + _dot(drhs_u.astype(mm), u0.astype(mm), _NT))
    # A = beta (K K^T . D) strictly lower, P = Q K^T . D lower.
    dad = da * x["Ds"]
    e = dad * x["kk"]
    m = e * beta + dp * x["pf"]
    dq, dkb = dqt * x["eG"], dkbar * x["ekb"]
    c_w, hk = _rows(drhs_w * kf), dkb * kf
    # G enters D (rows less columns of m), e^G (rhs_w, qt) and e^(G_C - G)
    # (kbar); G_C enters kbar and the chunk's decay.
    dG = _rows(m) + c_w * x["be"] + _rows(dq * qf - hk)
    dGl = _total(hk) + ddecay * x["decay"]
    dG = dG - _flip(jnp.sum(m, axis=0, keepdims=True))
    # G = cumsum(g), G_C the whole chunk's sum: dg_t = sum_{s >= t} dG_s +
    # dG_C, written as dbeta is: a row.
    to_row = lambda col, keep: jnp.sum(jnp.where(keep, col, 0.0), axis=0,
                                       keepdims=True)
    dg = to_row(dG, t >= s_i) + dGl
    dbeta = _rows(e) + c_w * x["eG"] + _rows(drhs_u * x["vf"])
    return (dq, drhs_w * x["be"] + dkb, dad * beta, dp * x["D"],
            drhs_u * beta, dg, to_row(dbeta, t == s_i), ds0)


def _scalar_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, t_ref,
                       do_ref, dsf_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                       db_ref, ds0_ref, ds_scr, *, scale, hb):
    """`_bwd_kernel` at one decay a head: dq and dk summed over a key
    head's r value heads here, dg written as dbeta is, [1, C] rows."""
    n = pl.program_id(2)
    dk, dv = ds_scr.shape[1:]
    r = hb * dk // q_ref.shape[2]

    @pl.when(n == 0)
    def _init():
        ds_scr[...] = dsf_ref[0]

    for i in range(hb // r):
        kl = slice(i * dk, (i + 1) * dk)
        q, k, qf, kf, kk, qk = _scalar_key_head(q_ref, k_ref, kl)
        shared = []  # (dq, dk, dKK, dQK) of each value head
        for j in range(i * r, (i + 1) * r):
            vl, h = slice(j * dv, (j + 1) * dv), pl.program_id(1) * hb + j
            x = _scalar_terms(qf, kf, v_ref[0, :, vl],
                              _head_col(g_ref[0], h),
                              _head_col(beta_ref[0], h), kk, qk, q.dtype)
            *terms, dv_j, dg, dbeta, ds0 = _scalar_bwd_head(
                x, t_ref[0, j, 0], st_ref[0, j, 0], do_ref[0, :, vl],
                ds_scr[j], scale)
            shared.append(terms)
            dv_ref[0, :, vl] = dv_j.astype(dv_ref.dtype)
            dg_ref[0, j] = dg
            db_ref[0, j] = dbeta
            ds_scr[j] = ds0
        dq, dk_, dkk, dqk = (_tree_sum(list(a)) for a in zip(*shared))
        dkk, dqk = dkk.astype(q.dtype), dqk.astype(q.dtype)
        dq_ref[0, :, kl] = (dq + _dot(dqk, k)).astype(dq_ref.dtype)
        dk_ref[0, :, kl] = (dk_ + _dot(dqk, q, _TN) + _dot(dkk, k)
                            + _dot(dkk, k, _TN)).astype(dk_ref.dtype)

    @pl.when(n == pl.num_programs(2) - 1)
    def _flush():
        ds0_ref[0] = ds_scr[...]


def _heads_per_step(H: int) -> int:
    return 2 if H % 2 == 0 else 1


def _specs(B, H, N, C, dk, dv, hb, kb, rev):
    """BlockSpecs over the grid (batch, head block, chunk). q, k, v, g lie
    [B, S, H*d]: a head is a d-lane column block, read where it lies; a grid
    step holds `hb` value heads and the `kb` key heads that serve them."""
    nn = (lambda n: N - 1 - n) if rev else (lambda n: n)
    tok = lambda w: pl.BlockSpec((1, C, w), lambda b, h, n: (b, nn(n), h))
    return dict(
        k=tok(kb * dk), v=tok(hb * dv),
        beta=pl.BlockSpec((1, C, H), lambda b, h, n: (b, nn(n), 0)),
        state=pl.BlockSpec((1, hb, dk, dv), lambda b, h, n: (b, h, 0, 0)),
        states=pl.BlockSpec((1, hb, 1, dk, dv),
                            lambda b, h, n: (b, h, nn(n), 0, 0)),
        tinv=pl.BlockSpec((1, hb, 1, C, C),
                          lambda b, h, n: (b, h, nn(n), 0, 0)),
        brow=pl.BlockSpec((1, hb, 1, C), lambda b, h, n: (b, h, 0, nn(n))))


def _call(kernel, rev, operands, ins, outs, C, scope, scratch=(), **kw):
    """The pallas_call every kernel makes, under the scope `scope`:
    `kda.core`, or `gdn.core` for a scalar-decay call
    (chipbench/reduce/scopes.py finds the core's device time by it; the
    backward rule is traced outside the mixer that opened its own).
    `operands` start with q, k, v, g, beta and end with a state
    [B, H, dk, dv]; `ins` are their `_specs` keys, `outs` (key, dtype) of
    each result. A grid step holds two neighbouring key heads
    (`_heads_per_step`: independent chains for the scheduler) and the value
    heads each serves."""
    q, v, beta = operands[0], operands[2], operands[4]
    B, S, H = beta.shape
    dk, dv, N = operands[-1].shape[2], v.shape[-1] // H, S // C
    r = H * dk // q.shape[-1]           # value heads a key head
    kb = _heads_per_step(H // r)
    hb = kb * r
    sp = _specs(B, H, N, C, dk, dv, hb, kb, rev)
    shape = dict(k=q.shape, v=v.shape, state=(B, H, dk, dv),
                 states=(B, H, N, dk, dv), tinv=(B, H, N, C, C),
                 brow=(B, H, 1, S))
    with jax.named_scope(scope):
        return pl.pallas_call(
            functools.partial(kernel, hb=hb, **kw),
            grid=(B, H // hb, N),
            in_specs=[sp[i] for i in ins],
            out_specs=[sp[o] for o, _ in outs],
            out_shape=[jax.ShapeDtypeStruct(shape[o], dt) for o, dt in outs],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32), *scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=dispatch.interpret(),
        )(*operands)


def _kda_fwd_call(q, k, v, g, beta, s0, scale, C, sub, scope):
    """q, k, g [B, S, H*dk], v [B, S, H*dv], beta [B, S, H], s0 [B,H,dk,dv]
    -> o [B, S, H*dv], the final state, the chunk-start states
    [B,H,N,dk,dv] float32 and the chunks' inverses [B,H,N,C,C]."""
    return _call(
        _fwd_kernel, False, (q, k, v, g, beta, s0),
        ins=("k", "k", "v", "k", "beta", "state"),
        outs=(("v", v.dtype), ("state", _F32), ("states", _F32),
              ("tinv", q.dtype)),
        C=C, scope=scope, scratch=[pltpu.VMEM((C, C), _F32)], scale=scale,
        sub=sub)


def _kda_bwd_call(q, k, v, g, beta, states, tinv, do, dsf, scale, C, sub,
                  scope):
    dq, dk, dv, dg, db, ds0 = _call(
        _bwd_kernel, True, (q, k, v, g, beta, states, tinv, do, dsf),
        ins=("k", "k", "v", "k", "beta", "states", "tinv", "v", "state"),
        outs=(("k", q.dtype), ("k", k.dtype), ("v", v.dtype), ("k", _F32),
              ("brow", _F32), ("state", _F32)),
        C=C, scope=scope, scale=scale, sub=sub)
    return dq, dk, dv, dg, jnp.swapaxes(db[:, :, 0], 1, 2), ds0


def _gdn_fwd_call(q, k, v, g, beta, s0, scale, C, sub, scope):
    """`_kda_fwd_call` at one decay a head: q, k [B, S, H_k*dk], g [B, S, H]
    as beta; `sub` has nothing to cut."""
    return _call(
        _scalar_fwd_kernel, False, (q, k, v, g, beta, s0),
        ins=("k", "k", "v", "beta", "beta", "state"),
        outs=(("v", v.dtype), ("state", _F32), ("states", _F32),
              ("tinv", q.dtype)),
        C=C, scope=scope, scratch=[pltpu.VMEM((C, C), _F32)], scale=scale)


def _gdn_bwd_call(q, k, v, g, beta, states, tinv, do, dsf, scale, C, sub,
                  scope):
    dq, dk, dv, dg, db, ds0 = _call(
        _scalar_bwd_kernel, True, (q, k, v, g, beta, states, tinv, do, dsf),
        ins=("k", "k", "v", "beta", "beta", "states", "tinv", "v", "state"),
        outs=(("k", q.dtype), ("k", k.dtype), ("v", v.dtype), ("brow", _F32),
              ("brow", _F32), ("state", _F32)),
        C=C, scope=scope, scale=scale)
    rows = lambda a: jnp.swapaxes(a[:, :, 0], 1, 2)
    return dq, dk, dv, rows(dg), rows(db), ds0


def _calls(g, beta):
    """(forward call, backward call) by what they observe: g [B, S, H], as
    beta lies, is one decay a head; [B, S, H*dk] one a channel."""
    return ((_gdn_fwd_call, _gdn_bwd_call) if g.shape == beta.shape
            else (_kda_fwd_call, _kda_bwd_call))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _kda_kernels(q, k, v, g, beta, s0, scale, C, sub, scope):
    o, sf, _, _ = _calls(g, beta)[0](q, k, v, g, beta, s0, scale, C, sub,
                                     scope)
    return o, sf


def _vjp_fwd(q, k, v, g, beta, s0, scale, C, sub, scope):
    o, sf, states, tinv = _calls(g, beta)[0](q, k, v, g, beta, s0, scale, C,
                                             sub, scope)
    o, states, tinv = (checkpoint_name(a, n) for a, n in zip(
        (o, states, tinv), RESIDUAL_NAMES))
    return (o, sf), (q, k, v, g, beta, states, tinv)


def _vjp_bwd(scale, C, sub, scope, res, cts):
    do, dsf = cts
    return _calls(res[3], res[4])[1](*res, do, dsf.astype(_F32), scale, C,
                                     sub, scope)


_kda_kernels.defvjp(_vjp_fwd, _vjp_bwd)


def kda_chunked_pallas(q, k, v, g, beta, *, chunk: int = 128, sub: int = 32,
                       scale: Optional[float] = None,
                       initial_state: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """`kda_chunked_xla` as two Pallas kernels under a custom VJP (module
    docstring). The dispatcher `kda_chunked` comes here on the TPU; tests
    come here directly and run the kernels in interpret mode."""
    scope = "gdn.core" if g.ndim == 3 else "kda.core"
    if g.ndim == 4:  # a decay a head takes its operands as they lie
        q, k, g = _per_channel(q, k, v, g)
    B, S, H, dv = v.shape
    dk = q.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    C = chunk
    sub = min(sub, C)
    assert C % sub == 0 and sub & (sub - 1) == 0, (C, sub)
    q, k, v, g, beta = _pad_to_chunks(q, k, v, g, beta, C)
    flat = lambda a: a.reshape(B, a.shape[1], -1) if a.ndim == 4 else a
    s0 = (jnp.zeros((B, H, dk, dv), _F32) if initial_state is None
          else initial_state.astype(_F32))
    o, s = _kda_kernels(flat(q), flat(k.astype(q.dtype)),
                        flat(v.astype(q.dtype)), flat(g.astype(_F32)),
                        beta.astype(_F32), s0, scale, C, sub, scope)
    return o.reshape(B, -1, H, dv)[:, :S].astype(v.dtype), s


# ------------------------------------------------- the mixers' convolution
#
# y = [l2norm_128](SiLU(short_conv(x, w) [+ b])) as one forward and one
# backward Pallas kernel under a `jax.custom_vjp`, over x [B, S, C] row-major:
# the layout a projection's product writes and the cores' kernels read, so
# between a projection and a core there are two device ops a tensor. Both
# kernels take a block of rows by `_CONV_COLS` lanes and, by a second
# BlockSpec on the same array, the `_HALO` rows before it (zeros at a
# sequence's start); the block is widened once into a float32 scratch, and
# worked `_CONV_CHUNK` rows by `_CONV_WORK` lanes at a time in a loop, so
# that a piece's values stay in registers from the taps to the store and the
# kernel's trace is one piece long: a tap is the piece's
# aligned window of rows, rotated along the sublanes (`pltpu.roll`; a load at
# a row that is no multiple of 8 costs more). The arithmetic is float32 (taps
# in w's own dtype, the norm's sum and rsqrt, SiLU(c) = c/2 + c/2 tanh(c/2):
# one transcendental and no division) with ONE rounding to x's dtype at the
# store: no step is less precise than the XLA body's bfloat16 multiplies and
# float32 norm. The backward walks a sequence's blocks last to first,
# recomputes the convolution, the logistic and the norm's statistic from x,
# w, b (the only residuals: nothing is named in `RESIDUAL_NAMES`, so a remat
# policy keeps what it kept), carries the first `_HALO` rows of dc in scratch
# for the rows before them (dx_t takes the next K - 1 rows' terms), and
# accumulates dw and db in float32 scratch across a column block's batches
# and row blocks, written once as one [8, C] array (dw's K rows, then db's).
# At [1,16384,4096] bfloat16, K 4, the forward takes 0.69 ms and the pair
# 1.17 with the norm, 0.56 and 0.91 without (268 MB at 819 GB/s is 0.33;
# XLA's formulation 2.32 and 7.39: benchmarks/probe_kda.py conv; PERF.md
# section 6, PR 52).

_HALO = 16          # rows: a bfloat16 tile; the last K - 1 <= 6 are read
_CONV_ROWS = (1024, 512)  # a block's rows, forward and backward
_CONV_COLS = (512, 256, 128)  # a block's lanes: the first that divides C
_CONV_CHUNK = 16    # rows worked at a time inside a block
_CONV_WORK = 512    # and lanes
_CONV_UNROLL = 4    # chunks a turn of the loop over a block's rows
_L2 = 128           # the norm's group: a head's columns, one vreg row
_L2_EPS = 1e-6      # `l2_normalize`'s


def _each_piece(bs, bc, body, last_first=False, work=None):
    """`body(first row, lane slice)` for a block's pieces of `_CONV_CHUNK`
    rows by `work` (`_CONV_WORK`) lanes: the rows in a loop on the device (a
    traced body a lane slice, not one a piece: a kernel's trace is set-up
    time), first to last or last to first."""
    work = work or _CONV_WORK
    n = bs // _CONV_CHUNK
    u = _CONV_UNROLL if n % _CONV_UNROLL == 0 else 1

    def rows(i, _):
        for j in range(u):
            at = i * u + j
            base = pl.multiple_of(
                ((n - 1 - at) if last_first else at) * _CONV_CHUNK,
                _CONV_CHUNK)
            for c in range(0, bc, work):
                body(base, pl.ds(c, min(work, bc)))
        return _

    jax.lax.fori_loop(0, n // u, rows, None)


def _rows_at(ref, row, cs, shift):
    """ref[row + shift : row + shift + chunk, cs] for -8 <= shift <= 8: the
    aligned window that holds them, rotated."""
    if shift == 0:
        return ref[pl.ds(row, _CONV_CHUNK), cs]
    n = _CONV_CHUNK + 8
    if shift < 0:
        return pltpu.roll(ref[pl.ds(row - 8, n), cs], -shift, 0)[8:]
    return pltpu.roll(ref[pl.ds(row, n), cs], n - shift, 0)[:_CONV_CHUNK]


def _conv_taps(xe_ref, w_ref, b_ref, base, cs, K):
    """(the K taps x_{t-K+1+j}, c = sum_j w[j] tap_j (+ b)) [chunk, lanes]
    float32 for the block's rows base .. base + chunk, from the widened
    block, whose row r lies at `_HALO + r`."""
    taps = [_rows_at(xe_ref, _HALO + base, cs, j - (K - 1)) for j in range(K)]
    c = _tree_sum([t * w_ref[j:j + 1, cs].astype(_F32)
                   for j, t in enumerate(taps)])
    return taps, c if b_ref is None else c + b_ref[:, cs].astype(_F32)


def _silu(c):
    """(SiLU(c), the logistic of c): sigma(c) = 1/2 + 1/2 tanh(c/2)."""
    h = 0.5 * c
    th = jnp.tanh(h)
    return h + h * th, 0.5 + 0.5 * th


def _by_group(f, *arrays, group=_L2):
    """f over every `group`-lane group of [rows, lanes] arrays: what a
    group's row sum [rows, 1] takes part in."""
    return jnp.concatenate(
        [f(*(a[:, g:g + group] for a in arrays))
         for g in range(0, arrays[0].shape[1], group)], axis=-1)


def _row_sum(a):
    return jnp.sum(a, -1, keepdims=True)


def _fold8(a):
    """A row chunk's [`_CONV_CHUNK`, lanes] onto eight sublanes: what a
    weight's gradient adds to its float32 scratch a piece."""
    return _tree_sum([a[r:r + 8] for r in range(0, _CONV_CHUNK, 8)])


def _widen(x_ref, halo_ref, xe_ref, n, rows_left):
    """The block and the `_HALO` rows before it as float32 in `xe_ref`; a
    sequence's first block has zeros before it, and rows past the sequence's
    end (`rows_left`, None where the blocks are whole) read as zeros."""
    xe_ref[:_HALO] = jnp.where(n > 0, halo_ref[0].astype(_F32), 0.0)
    xf = x_ref[0].astype(_F32)
    if rows_left is not None:
        xf = jnp.where(_iota(xf.shape, 0) < rows_left, xf, 0.0)
    xe_ref[_HALO:] = xf


def _conv_fwd_kernel(x_ref, halo_ref, w_ref, *rest, K, l2, bias, S):
    b_ref = rest[0] if bias else None
    y_ref, xe_ref = rest[-2:]
    _widen(x_ref, halo_ref, xe_ref, pl.program_id(2), None)

    def piece(base, cs):
        s = _silu(_conv_taps(xe_ref, w_ref, b_ref, base, cs, K)[1])[0]
        if l2:
            s = _by_group(lambda g: g * jax.lax.rsqrt(
                _row_sum(g * g) + _L2_EPS), s)
        y_ref[0, pl.ds(base, _CONV_CHUNK), cs] = s.astype(y_ref.dtype)

    _each_piece(*x_ref.shape[1:], piece)


def _conv_bwd_kernel(x_ref, halo_ref, w_ref, *rest, K, l2, bias, S):
    b_ref = rest[0] if bias else None
    dy_ref, dx_ref, dwb_ref, xe_ref, dc_ref, acc_ref = rest[-6:]
    bs, bc = x_ref.shape[1:]
    b, n = pl.program_id(1), pl.program_id(2)
    nb = pl.num_programs(2) - 1 - n             # the block, last first
    ragged = S % bs != 0
    _widen(x_ref, halo_ref, xe_ref, nb, S - nb * bs if ragged else None)

    @pl.when(n == 0)
    def _():  # nothing follows a sequence's last block
        dc_ref[bs:] = jnp.zeros((_HALO, bc), _F32)

    @pl.when((b == 0) & (n == 0))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def norm_bwd(s, dy):  # y = s r, r = (sum s^2 + eps)^-1/2
        r = jax.lax.rsqrt(_row_sum(s * s) + _L2_EPS)
        y = s * r
        return r * (dy - y * _row_sum(dy * y))

    def piece(base, cs):
        taps, c = _conv_taps(xe_ref, w_ref, b_ref, base, cs, K)
        s, sig = _silu(c)
        ds = dy_ref[0, pl.ds(base, _CONV_CHUNK), cs].astype(_F32)
        if l2:
            ds = _by_group(norm_bwd, s, ds)
        dc = ds * (sig * (1.0 + c * (1.0 - sig)))
        if ragged:  # rows past the sequence's end: nothing, whatever dy holds
            row = _iota(dc.shape, 0) + (nb * bs + base)
            dc = jnp.where(row < S, dc, 0.0)
        dc_ref[pl.ds(base, _CONV_CHUNK), cs] = dc
        for j in range(K):
            acc_ref[j, :, cs] += _fold8(dc * taps[j])
        acc_ref[K, :, cs] += _fold8(dc)
        dx = _tree_sum([  # dx_t = sum_j w[j] dc_{t+K-1-j}
            _rows_at(dc_ref, base, cs, K - 1 - j)
            * w_ref[j:j + 1, cs].astype(_F32) for j in range(K)])
        dx_ref[0, pl.ds(base, _CONV_CHUNK), cs] = dx.astype(dx_ref.dtype)

    _each_piece(bs, bc, piece, last_first=True)
    dc_ref[bs:] = dc_ref[:_HALO]    # the block before this one reads them

    @pl.when((b == pl.num_programs(1) - 1) & (n == pl.num_programs(2) - 1))
    def _():
        dwb_ref[...] = jnp.zeros(dwb_ref.shape, _F32)
        for j in range(K + 1):  # dw's K rows, then db's
            dwb_ref[j:j + 1, :] = jnp.sum(acc_ref[j], 0, keepdims=True)


def _conv_blocks(x, w, rows):
    (B, S, C), K = x.shape, w.shape[0]
    assert C % _L2 == 0 and K <= 7, (C, K)
    bs = min(rows, -(-S // _HALO) * _HALO)
    bc = next(c for c in _CONV_COLS if C % c == 0)
    return B, S, C, K, bs, bc, -(-S // bs)


# Jitted on their own, as the SSD calls are (ops/ssd.py): a stack's mixers
# call these at two or three tensors a layer kind, and a program traces a
# kernel's body once a shape.
@functools.partial(jax.jit, static_argnames=("l2", "scope"))
def _conv_fwd_call(x, w, b, l2, scope):
    """x [B, S, C], w [K, C], b [1, C] or None -> y [B, S, C] in x's dtype."""
    B, S, C, K, bs, bc, N = _conv_blocks(x, w, _CONV_ROWS[0])
    row = lambda r, at: pl.BlockSpec((1, r, bc), lambda i, c, n: (i, at(n), c))
    col = lambda r: pl.BlockSpec((r, bc), lambda i, c, n: (0, c))
    hb = bs // _HALO
    ins = [row(bs, lambda n: n),
           row(_HALO, lambda n: jnp.maximum(n * hb - 1, 0)), col(K)]
    with jax.named_scope(scope):
        return pl.pallas_call(
            functools.partial(_conv_fwd_kernel, K=K, l2=l2,
                              bias=b is not None, S=S),
            grid=(B, C // bc, N),
            in_specs=ins + ([col(1)] if b is not None else []),
            out_specs=row(bs, lambda n: n),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            scratch_shapes=[pltpu.VMEM((_HALO + bs, bc), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=dispatch.interpret(),
        )(x, x, w, *(() if b is None else (b,)))


@functools.partial(jax.jit, static_argnames=("l2", "scope"))
def _conv_bwd_call(x, w, b, dy, l2, scope):
    """-> dx [B, S, C] in x's dtype and one float32 [8, C]: dw's K rows, then
    db's (a whole tile a column block, whatever K)."""
    B, S, C, K, bs, bc, N = _conv_blocks(x, w, _CONV_ROWS[1])
    row = lambda r, at: pl.BlockSpec((1, r, bc),
                                     lambda c, i, n: (i, at(N - 1 - n), c))
    col = lambda r: pl.BlockSpec((r, bc), lambda c, i, n: (0, c))
    hb = bs // _HALO
    blk = row(bs, lambda n: n)
    ins = [blk, row(_HALO, lambda n: jnp.maximum(n * hb - 1, 0)), col(K)]
    with jax.named_scope(scope):
        return pl.pallas_call(
            functools.partial(_conv_bwd_kernel, K=K, l2=l2,
                              bias=b is not None, S=S),
            grid=(C // bc, B, N),
            in_specs=ins + ([col(1)] if b is not None else []) + [blk],
            out_specs=[blk, col(8)],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((8, C), _F32)],
            scratch_shapes=[pltpu.VMEM((_HALO + bs, bc), _F32),
                            pltpu.VMEM((bs + _HALO, bc), _F32),
                            pltpu.VMEM((K + 1, 8, bc), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=dispatch.interpret(),
        )(x, x, w, *(() if b is None else (b,)), dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_kernels(x, w, b, l2, scope):
    return _conv_fwd_call(x, w, b, l2, scope)


def _conv_vjp_fwd(x, w, b, l2, scope):
    return _conv_fwd_call(x, w, b, l2, scope), (x, w, b)


def _conv_vjp_bwd(l2, scope, res, dy):
    x, w, b = res
    dx, dwb = _conv_bwd_call(x, w, b, dy, l2, scope)
    K = w.shape[0]
    return (dx, dwb[:K].astype(w.dtype),
            None if b is None else dwb[K:K + 1].astype(b.dtype))


_conv_kernels.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


def mixer_conv_pallas(x, w, b=None, *, l2: bool = False,
                      scope: str = "mixer.conv") -> jax.Array:
    """`mixer_conv`'s kernel pair (the comment above): x [B, S, ...ch],
    w [K, ...ch], b [...ch] or None; with `l2` the norm is over x's last
    axis, which has to be `_L2` wide. The dispatcher comes here on the TPU;
    tests come here directly and run the kernels in interpret mode."""
    B, S = x.shape[:2]
    assert not l2 or x.shape[-1] == _L2, x.shape
    y = _conv_kernels(x.reshape(B, S, -1), w.reshape(w.shape[0], -1),
                      None if b is None else b.reshape(1, -1), l2, scope)
    return y.reshape(x.shape)


def mixer_conv_xla(x, w, b=None, *, l2: bool = False) -> jax.Array:
    """The same function in plain XLA, as the mixers wrote it out before the
    kernels: the fallback, and the kernels' reference in the tests."""
    y = short_conv(x, w)
    if b is not None:
        y = y + b.astype(y.dtype)
    y = jax.nn.silu(y)
    return l2_normalize(y) if l2 else y


def use_conv_kernels(platform: str, channels: int, l2_group: Optional[int],
                     on_mesh: bool) -> bool:
    """The convolution path's dispatch rule, a pure function of what the
    code observes: the kernels where a Mosaic call can run
    (`dispatch.mosaic`), with the channels whole 128-lane tiles and the norm
    (where there is one) over groups of 128."""
    return (dispatch.mosaic(platform, on_mesh)
            and dispatch.whole(channels, of=_L2) and l2_group in (None, _L2))


def mixer_conv(x, w, b=None, *, l2: bool = False,
               scope: str = "mixer.conv") -> jax.Array:
    """A recurrent mixer's way from a projection to its core:
    [l2norm](SiLU(short_conv(x, w) [+ b])) over x [B, S, ...ch], the norm
    over the last axis. The Pallas pair where `use_conv_kernels` says so,
    under the device scope `scope` (the caller's mixer: the backward rule is
    traced outside it), else `mixer_conv_xla`. Each traced call counts once
    in the phase table as `mixer.conv.pallas` or `mixer.conv.xla` with what
    it observed."""
    s = dispatch.site()
    channels = math.prod(x.shape[2:])
    kernels = use_conv_kernels(s.platform, channels,
                               x.shape[-1] if l2 else None, s.on_mesh)
    dispatch.observe("mixer.conv", kernels, rows=x.shape[1],
                     channels=channels, taps=w.shape[0], l2=l2,
                     bias=b is not None)
    if kernels:
        return mixer_conv_pallas(x, w, b, l2=l2, scope=scope)
    return mixer_conv_xla(x, w, b, l2=l2)


# ------------------------------------------------- the mixers' gated norm
#
# out = RMSNorm_group(y) * w * act(gate)   (gate after: GDN, KDA)   or
# out = RMSNorm_group(y * act(gate)) * w   (gate first: Mamba-2)
#
# as one forward and one backward Pallas kernel under a `jax.custom_vjp`, over
# y, gate [B, S, C] row-major: the layout the cores' kernels write and the
# output projection reads, so that between a core and its projection there is
# one device op and no float32 [B, S, heads, 128] tensor, which XLA lays out
# its own way and copies (PERF.md section 6, PR 59). A block is
# `_norm_blocks`'s rows by whole groups of lanes (all C where the group is C);
# it is worked `_CONV_CHUNK` rows at a time in a device loop, a row chunk's
# lanes in parts of `_CONV_WORK` at most: a part holds whole groups (the
# statistic by `_by_group`), or a group whole parts (two passes over the row
# chunk: the sums, added part by part, then the results, each part read again
# from VMEM). The arithmetic is float32 (the mean of squares and rsqrt, the
# logistic as `_silu` has it) with ONE rounding to y's dtype at the store. The
# backward recomputes the statistic from y, gate, w (the only residuals:
# nothing is named in `RESIDUAL_NAMES`) and accumulates dw in float32 scratch
# across a column block's batches and row blocks, written once as eight
# sublanes' partial sums [8, C] (summed, and folded over the heads that share a
# weight, outside).
# At [1,16384,32,128] bfloat16 (SiLU after the norm) the forward takes 0.79 ms
# and forward + backward 1.91 (402 and 671 MB: 509 and 520 GB/s of 819); XLA's
# formulation of the same three lines 4.13 and 10.47; at [1,8192,32,128] (a
# sigmoid) 0.48 and 1.12 against 2.15 and 5.10; at [1,4096,4096] as one group
# (SiLU first) 0.30 and 0.69 against 0.38 and 0.79 (the two passes are the
# vector unit's); largest error over the float32 reference's largest value
# 0.0030 in the result and 0.0027 / 0.0020 in dy / dgate, XLA's body on the
# same operands alike (benchmarks/probe_kda.py norm; PERF.md section 6, PR 59).

_GATES = {"silu": jax.nn.silu, "sigmoid": jax.nn.sigmoid}


def _gate(act, c):
    """(act(c), act'(c)) float32 for "silu" or "sigmoid"."""
    s, sig = _silu(c)
    if act == "silu":
        return s, sig * (1.0 + c * (1.0 - sig))
    return sig, sig * (1.0 - sig)


def _norm_pieces(bs, bc, group, body):
    """`body(rows, [lane slices of one span])` for a block's row chunks, in
    `_each_piece`'s loop. A part is the widest of `_CONV_COLS` (at most
    `_CONV_WORK`) that holds whole groups of the block's `bc` lanes or that
    a group holds whole; a span is a part, or the group's parts."""
    part = next(c for c in _CONV_COLS if c <= _CONV_WORK and (
        (c % group == 0 and bc % c == 0) or group % c == 0))
    span = max(part, group)

    def piece(base, cs):
        body(pl.ds(base, _CONV_CHUNK),
             [pl.ds(cs.start + c, part) for c in range(0, span, part)])

    _each_piece(bs, bc, piece, work=span)


def _span_terms(parts, terms):
    """`terms(part)` as the two passes over a span ask for it: worked once
    where the span is one part (its values stay in registers), again at
    every asking where it is several (they would not)."""
    if len(parts) > 1:
        return terms
    held = terms(parts[0])
    return lambda p: held


def _group_sums(parts, products, group):
    """Every group's row sums of `products(part)` (a tuple of [rows, part]
    float32 arrays), a part: [rows, part] each where a part holds whole
    groups; where the span is one group of several parts the products are
    added part by part (a part's worth of registers a sum) and reduced along
    the lanes once, [rows, 1] for every part."""
    if group < parts[0].size:
        return [tuple(_by_group(lambda g: jnp.broadcast_to(_row_sum(g),
                                                           g.shape),
                                v, group=group) for v in products(p))
                for p in parts]
    acc = None
    for p in parts:
        vs = products(p)
        acc = vs if acc is None else tuple(a + v for a, v in zip(acc, vs))
    return [tuple(_row_sum(a) for a in acc)] * len(parts)


def _norm_fwd_kernel(y_ref, g_ref, w_ref, o_ref, *, group, act, first, eps):
    def span(rows, parts):
        def terms(p):  # what the norm takes, and the gate's value after it
            y = y_ref[0, rows, p].astype(_F32)
            a = _gate(act, g_ref[0, rows, p].astype(_F32))[0]
            return (y * a, None) if first else (y, a)

        terms = _span_terms(parts, terms)
        sums = _group_sums(parts, lambda p: (terms(p)[0] ** 2,), group)
        for p, (ss,) in zip(parts, sums):
            x, a = terms(p)
            out = x * jax.lax.rsqrt(ss * (1.0 / group) + eps) * w_ref[
                :, p].astype(_F32)
            o_ref[0, rows, p] = (out if first else out * a).astype(
                o_ref.dtype)

    _norm_pieces(*y_ref.shape[1:], group, span)


def _norm_bwd_kernel(y_ref, g_ref, w_ref, do_ref, dy_ref, dg_ref, dw_ref,
                     acc_ref, *, group, act, first, eps, S):
    bs, bc = y_ref.shape[1:]
    b, n = pl.program_id(1), pl.program_id(2)
    ragged = S % bs != 0

    @pl.when((b == 0) & (n == 0))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def span(rows, parts):
        def terms(p):  # x the norm took; t = d(x r); what the gate needs
            y = y_ref[0, rows, p].astype(_F32)
            a, da = _gate(act, g_ref[0, rows, p].astype(_F32))
            do = do_ref[0, rows, p].astype(_F32)
            w = w_ref[:, p].astype(_F32)
            if first:
                return y * a, do * w, (y, a, da, do)
            return y, do * (w * a), (w, a, da, do)

        terms = _span_terms(parts, terms)

        def products(p):
            x, t, _ = terms(p)
            return x * x, x * t

        for p, (ss, tx) in zip(parts, _group_sums(parts, products, group)):
            x, t, (u, a, da, do) = terms(p)
            r = jax.lax.rsqrt(ss * (1.0 / group) + eps)
            # n = x r;  dx = r (t - n mean(t n))
            dx = r * (t - x * (r * r * (1.0 / group)) * tx)
            nrm = x * r
            if first:   # x = y a;  out = n w
                dy, dg, dw = dx * a, dx * u * da, do * nrm
            else:       # x = y;    out = n w a
                dy, dg, dw = dx, do * nrm * (u * da), do * nrm * a
            dy_ref[0, rows, p] = dy.astype(dy_ref.dtype)
            dg_ref[0, rows, p] = dg.astype(dg_ref.dtype)
            if ragged:  # rows past the sequence's end reach no weight
                row = _iota(dw.shape, 0) + (n * bs + rows.start)
                dw = jnp.where(row < S, dw, 0.0)
            acc_ref[:, p] += _fold8(dw)

    _norm_pieces(bs, bc, group, span)

    @pl.when((b == pl.num_programs(1) - 1) & (n == pl.num_programs(2) - 1))
    def _():
        dw_ref[...] = acc_ref[...]


def _norm_blocks(y, group, rows):
    """(bs, bc, row blocks): lanes the first of `_CONV_COLS` that holds whole
    groups, or one group; rows so that a block is `rows` by `_CONV_COLS[0]`
    elements."""
    B, S, C = y.shape
    assert group % _L2 == 0 and C % group == 0, (C, group)
    bc = next((c for c in _CONV_COLS if C % c == 0 and c % group == 0), group)
    bs = min(max(rows * _CONV_COLS[0] // bc, _CONV_CHUNK),
             -(-S // _HALO) * _HALO)
    return bs, bc, -(-S // bs)


@functools.partial(jax.jit, static_argnames=("group", "act", "first", "eps",
                                             "scope"))
def _norm_fwd_call(y, gate, w, group, act, first, eps, scope):
    """y, gate [B, S, C], w [1, C] -> out [B, S, C] in y's dtype."""
    B, S, C = y.shape
    bs, bc, N = _norm_blocks(y, group, _CONV_ROWS[0])
    blk = pl.BlockSpec((1, bs, bc), lambda c, i, n: (i, n, c))
    with jax.named_scope(scope):
        return pl.pallas_call(
            functools.partial(_norm_fwd_kernel, group=group, act=act,
                              first=first, eps=eps),
            grid=(C // bc, B, N),
            in_specs=[blk, blk, pl.BlockSpec((1, bc), lambda c, i, n: (0, c))],
            out_specs=blk,
            out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=dispatch.interpret(),
        )(y, gate, w)


@functools.partial(jax.jit, static_argnames=("group", "act", "first", "eps",
                                             "scope"))
def _norm_bwd_call(y, gate, w, do, group, act, first, eps, scope):
    """-> dy, dgate [B, S, C] in their operands' dtypes and dw's eight
    partial sums, float32 [8, C]."""
    B, S, C = y.shape
    bs, bc, N = _norm_blocks(y, group, _CONV_ROWS[1])
    blk = pl.BlockSpec((1, bs, bc), lambda c, i, n: (i, n, c))
    col = lambda r: pl.BlockSpec((r, bc), lambda c, i, n: (0, c))
    with jax.named_scope(scope):
        return pl.pallas_call(
            functools.partial(_norm_bwd_kernel, group=group, act=act,
                              first=first, eps=eps, S=S),
            grid=(C // bc, B, N),
            in_specs=[blk, blk, col(1), blk],
            out_specs=[blk, blk, col(8)],
            out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                       jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                       jax.ShapeDtypeStruct((8, C), _F32)],
            scratch_shapes=[pltpu.VMEM((8, bc), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary")),
            interpret=dispatch.interpret(),
        )(y, gate, w, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _norm_kernels(y, gate, w, group, act, first, eps, scope):
    return _norm_fwd_call(y, gate, w, group, act, first, eps, scope)


def _norm_vjp_fwd(y, gate, w, group, act, first, eps, scope):
    return _norm_fwd_call(y, gate, w, group, act, first, eps, scope), (
        y, gate, w)


def _norm_vjp_bwd(group, act, first, eps, scope, res, do):
    dy, dg, dw = _norm_bwd_call(*res, do, group, act, first, eps, scope)
    return dy, dg, jnp.sum(dw, 0, keepdims=True).astype(res[2].dtype)


_norm_kernels.defvjp(_norm_vjp_fwd, _norm_vjp_bwd)


def _by_groups(w, group):
    """A weight a group shares ([group]) as it is, a weight a channel (any
    shape) as [groups, group]: either broadcasts against [..., groups,
    group]."""
    return w if w.shape == (group,) else w.reshape(-1, group)


def gated_norm_pallas(y, gate, w, *, group: int, gate_act: str,
                      gate_first: bool, eps: float,
                      scope: str = "mixer.gated_norm") -> jax.Array:
    """`gated_norm`'s kernel pair (the comment above): y, gate [B, S, ...ch],
    w broadcastable to [..., group] against the channels cut into groups (a
    weight a head is repeated over the heads here, so that its gradient is
    summed over them by autodiff). The dispatcher comes here on the TPU;
    tests come here directly and run the kernels in interpret mode."""
    B, S = y.shape[:2]
    C = math.prod(y.shape[2:])
    wc = jnp.broadcast_to(_by_groups(w, group), (C // group, group))
    out = _norm_kernels(y.reshape(B, S, C), gate.reshape(B, S, C),
                        wc.reshape(1, C), group, gate_act, gate_first, eps,
                        scope)
    return out.reshape(y.shape)


def gated_norm_xla(y, gate, w, *, group: int, gate_act: str,
                   gate_first: bool, eps: float) -> jax.Array:
    """The same function in plain XLA, as the three mixers wrote it out
    before the kernels, op for op (a preset's CPU program is the one it was:
    tests/test_model_table.py): the fallback, and the kernels' reference in
    the tests. Float32 inside and one rounding to y's dtype, but for the
    "sigmoid" gate after the norm (KDA's): its mixer rounded the norm to the
    model's dtype and gated there, and so does this."""
    dt, act = y.dtype, _GATES[gate_act]
    grouped = lambda a: a.reshape(a.shape[:2] + (-1, group))

    def norm(x):  # float32 over a group's channels
        wg = _by_groups(w, group)
        x2 = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(x2 + eps) * wg.astype(_F32)

    if gate_first:
        x = grouped(y.astype(_F32) * act(gate.astype(_F32)))
        return norm(x).reshape(y.shape).astype(dt)
    out = norm(grouped(y.astype(_F32)))
    if gate_act == "sigmoid":
        return (out.astype(dt) * grouped(act(gate))).reshape(y.shape)
    return (out * grouped(act(gate.astype(_F32)))).astype(dt).reshape(y.shape)


def use_norm_kernels(platform: str, channels: int, group: int,
                     on_mesh: bool) -> bool:
    """The gated norm's dispatch rule, a pure function of what the code
    observes: the kernels where a Mosaic call can run (`dispatch.mosaic`),
    with the channels whole 128-lane tiles and the group a multiple of 128
    that divides them."""
    return (dispatch.mosaic(platform, on_mesh)
            and dispatch.whole(channels, group, of=_L2)
            and channels % group == 0)


def gated_norm(y, gate, w, *, group: int, gate_act: str, gate_first: bool,
               eps: Optional[float],
               scope: str = "mixer.gated_norm") -> jax.Array:
    """A recurrent mixer's way from its core to its output projection: an
    RMSNorm over every `group` channels of y [B, S, ...ch] times `w`, and
    `gate_act` ("silu", "sigmoid") of `gate` times the result, or times y
    before the norm (`gate_first`); what a caller passes is what its layer
    kind IS (`eps` None: an RMSNorm's 1e-6). The Pallas pair where
    `use_norm_kernels` says so, under the device scope `scope` (the caller's
    mixer, never its core), else `gated_norm_xla`. Each traced call counts
    once in the phase table as `mixer.gated_norm.pallas` or
    `mixer.gated_norm.xla` with what it observed."""
    s = dispatch.site()
    channels = math.prod(y.shape[2:])
    kernels = use_norm_kernels(s.platform, channels, group, s.on_mesh)
    dispatch.observe("mixer.gated_norm", kernels, rows=y.shape[1],
                     channels=channels, group=group, gate=gate_act,
                     gate_first=gate_first)
    kw = dict(group=group, gate_act=gate_act, gate_first=gate_first,
              eps=1e-6 if eps is None else eps)
    if kernels:
        return gated_norm_pallas(y, gate, w, scope=scope, **kw)
    return gated_norm_xla(y, gate, w, **kw)


# ---------------------------------------------------------------- dispatch


def use_kernels(platform: str, d_k: int, d_v: int, chunk: int,
                on_mesh: bool) -> bool:
    """The dispatch rule, a pure function of what the code observes: the
    kernels where a Mosaic call can run (`dispatch.mosaic`), with keys and
    values whole 128-lane tiles and a chunk of 128, the one the kernels
    compile for: at 256 Mosaic refuses them (`tests/
    test_kda_kernel_compile.py`, `test_a_chunk_of_256_is_refused_for_v5e`)."""
    return (dispatch.mosaic(platform, on_mesh) and dispatch.whole(d_k, d_v)
            and chunk == 128)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 128, sub: int = 32,
                scale: Optional[float] = None,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The chunked delta rule, same arguments and results as
    `kda_recurrent`: the Pallas kernels where `use_kernels` says so, else
    `kda_chunked_xla`. Each traced call counts once in the phase table
    (layers under one scan trace once): a per-channel call as
    `kda.core.pallas` or `kda.core.xla`, a scalar-decay one as
    `gdn.core.pallas` or `gdn.core.xla` with what it observed (`chunk`,
    `chunks`, `k_heads`, `v_heads`, `decay="head"`) and the body that runs
    it: `body="scalar"` (the kernels take the rule as it is) or
    `"per_channel"` (the XLA body's broadcast)."""
    s = dispatch.site()
    kernels = use_kernels(s.platform, q.shape[-1], v.shape[-1], chunk,
                          s.on_mesh)
    if g.ndim == 3:
        dispatch.observe("gdn.core", kernels, chunk=chunk,
                         chunks=-(-q.shape[1] // chunk), k_heads=q.shape[2],
                         v_heads=v.shape[2], decay="head",
                         body="scalar" if kernels else "per_channel")
    else:
        dispatch.observe("kda.core", kernels)
    body = kda_chunked_pallas if kernels else kda_chunked_xla
    return body(q, k, v, g, beta, chunk=chunk, sub=sub, scale=scale,
                initial_state=initial_state)
