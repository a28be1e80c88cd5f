"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

The reference has no sequence/context parallelism anywhere (SURVEY.md §5.7 —
verified gap); long-context is a first-class requirement here. Each device
holds a contiguous sequence chunk of Q/K/V. K/V chunks rotate around the
`seq` mesh axis via `lax.ppermute` (ICI neighbor hops); every step each
device computes flash attention between its Q chunk and the visiting K/V
chunk and folds the result into running (out, logsumexp) statistics — the
blockwise-parallel formulation, so the full S×S score matrix never exists
and per-device memory is O(S_local).

Causality at chunk granularity is decided by a 3-way `lax.switch` (visiting
chunk entirely in the future → skip; same chunk → causal flash; entirely in
the past → non-causal flash), so ~half the FLOPs are skipped at runtime
without data-dependent Python control flow.

The whole ring is one `jax.custom_vjp`: the backward pass re-runs the ring,
recomputing per-chunk probabilities from the *global* logsumexp (saved from
forward) and rotating (k, v, dk, dv) together so each chunk's gradient
arrives home after a full revolution. Compute uses the same Pallas backward
kernels as single-chip flash attention (flash_bwd_core).

Must be called inside `shard_map` over a mesh with the `axis_name` axis;
inputs are the per-device shards in model layout [B, S_local, H|KVH, D].
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import _flash_fwd, flash_bwd_core

_NEG_INF = -1e30


def _merge(o, lse, o_c, lse_c):
    """Fold chunk (o_c, lse_c) into running (o, lse); all f32, o [B,H,S,D],
    lse [B,H,S] (the flash kernels' shape for the statistics)."""
    lse_new = jnp.logaddexp(lse, lse_c)
    # Rows with no valid keys yet have lse == lse_c == -inf; keep them zero.
    w_old = jnp.where(lse == _NEG_INF * 1.0, 0.0, jnp.exp(lse - lse_new))
    w_new = jnp.where(lse_c == _NEG_INF * 1.0, 0.0, jnp.exp(lse_c - lse_new))
    return o * w_old[..., None] + o_c * w_new[..., None], lse_new


def _ring_perm(sp: int):
    return [(r, (r + 1) % sp) for r in range(sp)]


def _ring_fwd_impl(q, k, v, axis_name, causal, scale, block):
    """q [B,H,S,D], k/v [B,KVH,S,D] shards -> (o f32, lse [B,H,S] f32)."""
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, S, D = q.shape

    def full_chunk(q, kc, vc):
        o, lse = _flash_fwd(q, kc, vc, scale, False, block, block)
        return o.astype(jnp.float32), lse

    def diag_chunk(q, kc, vc):
        o, lse = _flash_fwd(q, kc, vc, scale, True, block, block)
        return o.astype(jnp.float32), lse

    def skip_chunk(q, kc, vc):
        return (jnp.zeros((B, H, S, D), jnp.float32),
                jnp.full((B, H, S), _NEG_INF, jnp.float32))

    o = jnp.zeros((B, H, S, D), jnp.float32)
    lse = jnp.full((B, H, S), _NEG_INF, jnp.float32)
    kc, vc = k, v
    for step in range(sp):
        j = (my - step) % sp
        if causal:
            # 0: j > my (future, skip) / 1: j == my (diagonal) / 2: past.
            idx = jnp.clip(jnp.sign(my - j) + 1, 0, 2)
            o_c, lse_c = jax.lax.switch(
                idx, [skip_chunk, diag_chunk, full_chunk], q, kc, vc)
        else:
            o_c, lse_c = full_chunk(q, kc, vc)
        o, lse = _merge(o, lse, o_c, lse_c)
        if step < sp - 1:
            kc = jax.lax.ppermute(kc, axis_name, _ring_perm(sp))
            vc = jax.lax.ppermute(vc, axis_name, _ring_perm(sp))
    return o, lse


def _ring_bwd_impl(q, k, v, do, lse, delta, axis_name, causal, scale, block):
    """Backward ring: rotate (kc, vc, dkc, dvc) together; after sp rotations
    each chunk's accumulated gradient is back on its owner."""
    sp = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    def full_chunk(q, kc, vc, do):
        return flash_bwd_core(q, kc, vc, do, lse, delta, scale=scale,
                              causal=False, block_q=block, block_k=block)

    def diag_chunk(q, kc, vc, do):
        return flash_bwd_core(q, kc, vc, do, lse, delta, scale=scale,
                              causal=True, block_q=block, block_k=block)

    def skip_chunk(q, kc, vc, do):
        return (jnp.zeros_like(q), jnp.zeros_like(kc), jnp.zeros_like(vc))

    dq = jnp.zeros(q.shape, jnp.float32)
    kc, vc = k, v
    dkc = jnp.zeros(k.shape, jnp.float32)
    dvc = jnp.zeros(v.shape, jnp.float32)
    for step in range(sp):
        j = (my - step) % sp
        if causal:
            idx = jnp.clip(jnp.sign(my - j) + 1, 0, 2)
            dq_c, dk_c, dv_c = jax.lax.switch(
                idx, [skip_chunk, diag_chunk, full_chunk], q, kc, vc, do)
        else:
            dq_c, dk_c, dv_c = full_chunk(q, kc, vc, do)
        dq = dq + dq_c.astype(jnp.float32)
        dkc = dkc + dk_c.astype(jnp.float32)
        dvc = dvc + dv_c.astype(jnp.float32)
        # dk/dv rotate every step (sp total) so the visiting chunk's gradient
        # travels the remaining arc back to its owner; k/v are dead after the
        # last compute step, so skip their final hop.
        if step < sp - 1:
            kc = jax.lax.ppermute(kc, axis_name, _ring_perm(sp))
            vc = jax.lax.ppermute(vc, axis_name, _ring_perm(sp))
        dkc = jax.lax.ppermute(dkc, axis_name, _ring_perm(sp))
        dvc = jax.lax.ppermute(dvc, axis_name, _ring_perm(sp))
    return dq.astype(q.dtype), dkc.astype(k.dtype), dvc.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring(q, k, v, axis_name, causal, scale, block):
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal, scale, block)
    return o.astype(q.dtype)


def _ring_vjp_fwd(q, k, v, axis_name, causal, scale, block):
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal, scale, block)
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, scale, block, res, g):
    q, k, v, o, lse = res
    do = g.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return _ring_bwd_impl(q, k, v, do, lse, delta, axis_name, causal, scale,
                          block)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(
    q: jax.Array,  # [B, S_local, H, D] shard
    k: jax.Array,  # [B, S_local, KVH, D] shard
    v: jax.Array,  # [B, S_local, KVH, D] shard
    axis_name: str,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block: int = 128,
) -> jax.Array:
    """Sequence-parallel exact attention; call inside shard_map."""
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = _ring(qt, kt, vt, axis_name, causal, scale, block)
    return jnp.swapaxes(ot, 1, 2)
