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

Plain XLA, differentiable by autodiff; no kernel yet (ROADMAP, Speed).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

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


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int = 256,
                initial_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD in plain XLA, any sequence length. Same arguments and
    results as `ssd_recurrent`. Each traced call counts once in the phase
    table as `ssd.core.xla`, with its chunk, chunks, heads and state as
    attributes (layers under one scan trace once)."""
    from ray_tpu.util import tracing

    Bz, S, H, P = x.shape
    G, N = B.shape[-2:]
    R, mm = H // G, x.dtype
    x, dt, B, C = _pad_to_chunks(x, dt, B, C, chunk)
    n = x.shape[1] // chunk
    tracing.observe("ssd.core.xla", 0, slow=False, chunk=chunk, chunks=n,
                    heads=H, state=N)

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
