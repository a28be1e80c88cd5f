"""Chunked fused lm-head + cross-entropy (vocab-blocked, custom VJP).

At the bench shape (M = 8*1024 tokens, V = 32000, f32) the plain pipeline
``logits = x @ head; CE(logits)`` materializes a ~1 GB logits tensor in the
forward AND a ~1 GB dlogits tensor in the backward — pure HBM traffic the
MXU waits on. This op never forms either: the forward scans vocab chunks
with an ONLINE logsumexp (running max/sum, flash-attention style) keeping
only [M] statistics, and the backward recomputes each chunk's logits,
forms its dlogits tile, and immediately contracts it into the dx / dhead
accumulators. Peak extra memory is one [M, chunk] tile instead of [M, V].

Role parity: the reference trains with torch's fused/flash CE epilogues
(e.g. fused linear-cross-entropy in its model stacks); this is the
XLA-native equivalent — lax.scan compiles one chunk body whatever the
vocabulary size, and every matmul is an MXU-shaped [M,d]x[d,C] tile. Numerics: logits accumulate in f32 regardless of x/head dtype;
verified against the unfused path on CPU to 1e-5 (tests/test_fused_ce.py).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


def _pick_chunk(V: int, target: int = 4096) -> int:
    """Chunk width for a vocab of V: the largest 128-multiple divisor
    <= target (MXU lane width) if one exists, else the largest divisor
    <= target, else V itself (a single chunk — V with no usable divisor,
    e.g. a prime vocab, must NOT degrade to a V-step scan of [M,1]
    matmuls)."""
    best_any = 0
    for c in range(min(target, V), 1, -1):
        if V % c == 0:
            if c % 128 == 0:
                return c  # descending: first 128-multiple is the largest
            if best_any == 0:
                best_any = c
    return best_any or V


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_ce(x: jax.Array, head: jax.Array, targets: jax.Array,
             valid: jax.Array, chunk: int = 0) -> jax.Array:
    """Mean next-token CE of ``(x @ head)`` vs ``targets``.

    x: [M, d] (any float dtype; matmuls accumulate f32)
    head: [d, V]
    targets: [M] int32; valid: [M] f32 weights (0 masks a position)
    """
    loss, _ = _fwd_stats(x, head, targets, valid, chunk)
    return loss


def _fwd_stats(x, head, targets, valid, chunk):
    tgt_logit, lse = _token_stats(x, head, targets, chunk)
    denom = jnp.maximum(valid.sum(), 1.0)
    loss = -(((tgt_logit - lse) * valid).sum() / denom)
    return loss, (lse,)


def _token_stats(x, head, targets, chunk):
    """(the target's logit, logsumexp of the row) of every row of x @ head,
    [M] float32 each."""
    M, d = x.shape
    V = head.shape[1]
    C = chunk or _pick_chunk(V)
    n = V // C
    head_c = head.reshape(d, n, C).transpose(1, 0, 2)  # [n, d, C]

    def body(carry, inp):
        m, s, tgt_logit = carry
        hc, ci = inp
        logits = jnp.dot(x, hc, preferred_element_type=jnp.float32)  # [M,C]
        cmax = logits.max(axis=-1)
        new_m = jnp.maximum(m, cmax)
        # Online logsumexp: rescale the running sum to the new max.
        s = s * jnp.exp(m - new_m) + jnp.exp(
            logits - new_m[:, None]).sum(-1)
        # Gather the target logit if it falls in this chunk.
        local = targets - ci * C
        in_chunk = (local >= 0) & (local < C)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, C - 1)[:, None], axis=1)[:, 0]
        tgt_logit = jnp.where(in_chunk, picked, tgt_logit)
        return (new_m, s, tgt_logit), None

    init = (jnp.full((M,), -jnp.inf, jnp.float32),
            jnp.zeros((M,), jnp.float32),
            jnp.zeros((M,), jnp.float32))
    (m, s, tgt_logit), _ = jax.lax.scan(
        body, init, (head_c, jnp.arange(n)))
    return tgt_logit, m + jnp.log(s)


def _fused_ce_fwd(x, head, targets, valid, chunk):
    loss, (lse,) = _fwd_stats(x, head, targets, valid, chunk)
    return loss, (x, head, targets, valid, lse)


def _fused_ce_bwd(chunk, res, g):
    x, head, targets, valid, lse = res
    denom = jnp.maximum(valid.sum(), 1.0)
    w = (g * valid / denom).astype(jnp.float32)  # [M] dloss/dll * -1 later
    return _token_bwd(x, head, targets, lse, w, chunk) + (None, None)


def _token_bwd(x, head, targets, lse, w, chunk):
    """(dx, dhead) of sum_i w_i CE_i: `w` [M] float32 the cotangent of every
    row's cross-entropy."""
    M, d = x.shape
    V = head.shape[1]
    C = chunk or _pick_chunk(V)
    n = V // C
    head_c = head.reshape(d, n, C).transpose(1, 0, 2)  # [n, d, C]

    def body(dx, inp):
        hc, ci = inp
        logits = jnp.dot(x, hc, preferred_element_type=jnp.float32)
        p = jnp.exp(logits - lse[:, None])  # softmax chunk [M, C]
        local = targets - ci * C
        in_chunk = (local >= 0) & (local < C)
        onehot = (jax.nn.one_hot(jnp.clip(local, 0, C - 1), C,
                                 dtype=jnp.float32)
                  * in_chunk[:, None].astype(jnp.float32))
        dlogits = (p - onehot) * w[:, None]  # [M, C] — one tile, not [M,V]
        dx = dx + jnp.dot(dlogits, hc.T.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        dhead_c = jnp.dot(x.T.astype(jnp.float32), dlogits,
                          preferred_element_type=jnp.float32)  # [d, C]
        return dx, dhead_c

    dx, dhead_chunks = jax.lax.scan(
        body, jnp.zeros((M, d), jnp.float32), (head_c, jnp.arange(n)))
    dhead = dhead_chunks.transpose(1, 0, 2).reshape(d, V)
    return dx.astype(x.dtype), dhead.astype(head.dtype)


fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_token_ce(x: jax.Array, head: jax.Array, targets: jax.Array,
                   chunk: int = 0) -> jax.Array:
    """The cross-entropy of EVERY row of ``(x @ head)`` vs ``targets``, [M]
    float32, where `fused_ce` returns their weighted mean: for a loss that
    weighs a token's cross-entropy by something of the token's own (a looped
    stack's exit distribution, models/transformer.py). The same vocab-chunk
    scans; the backward takes a cotangent a row."""
    tgt_logit, lse = _token_stats(x, head, targets, chunk)
    return lse - tgt_logit


def _fused_token_ce_fwd(x, head, targets, chunk):
    tgt_logit, lse = _token_stats(x, head, targets, chunk)
    return lse - tgt_logit, (x, head, targets, lse)


def _fused_token_ce_bwd(chunk, res, g):
    x, head, targets, lse = res
    return _token_bwd(x, head, targets, lse, g.astype(jnp.float32),
                      chunk) + (None,)


fused_token_ce.defvjp(_fused_token_ce_fwd, _fused_token_ce_bwd)


def fused_next_token_loss(x: jax.Array, head: jax.Array,
                          targets: jax.Array, valid: jax.Array,
                          chunk: int = 0) -> jax.Array:
    """[B, S, d] hidden states -> mean CE, flattened for the op."""
    B, S, d = x.shape
    return fused_ce(x.reshape(B * S, d), head,
                    targets.reshape(B * S).astype(jnp.int32),
                    valid.reshape(B * S).astype(jnp.float32), chunk)
