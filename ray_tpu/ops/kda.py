"""Kimi Delta Attention (KDA): a gated delta rule with a per-channel decay.

Per head, with a state S [d_k, d_v] in place of keys and values:

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale,        a_t = exp(g_t), g_t <= 0 per key channel

`kda_recurrent` is that recurrence, one token at a time (tests, and the shape
a decode step will take). `kda_chunked` is the form the training path runs:
the sequence is cut into chunks of `chunk` tokens; inside a chunk the
rank-one updates are folded into one triangular system (the WY / UT
transform of the delta rule), solved for all chunks at once; between chunks
a `lax.scan` carries S. With G_t the decay summed in log space from the
chunk's start (float32), u_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t):

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

Everything is differentiable by plain autodiff; the matmuls take operands in
the inputs' dtype (bfloat16 on the chip) and accumulate in float32; the
triangular inverse, the decay and the state are float32. XLA only: a Pallas
kernel for the chunk body is the obvious next step (PERF.md section 7).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

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


def kda_recurrent(q, k, v, g, beta, *, scale: Optional[float] = None,
                  initial_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Token-by-token KDA. q, k [B,S,H,dk]; v [B,S,H,dv]; g [B,S,H,dk] (log
    decay); beta [B,S,H] -> (o [B,S,H,dv] in v's dtype, S [B,H,dk,dv] f32)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
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


def kda_chunked(q, k, v, g, beta, *, chunk: int = 128, sub: int = 32,
                scale: Optional[float] = None,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked KDA, same arguments and results as `kda_recurrent`. `chunk`
    is 16 * 2^j (128 fills the MXU's tile); a sequence that is not a
    multiple of it is padded at its end with tokens that leave the state as
    it is (k = v = beta = g = 0)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    scale = dk ** -0.5 if scale is None else scale
    C = chunk
    sub = min(sub, C)
    pad = (-S) % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    N, n = (S + pad) // C, C // sub
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
