"""Pallas TPU flash attention (forward + backward), GQA-aware.

The reference framework ships no attention kernels (it delegates the model
math to torch; SURVEY.md §5.7 — long-context is a first-class gap to fill).
Here the flash kernel is the MFU-critical op: online-softmax tiling keeps the
S×S logits out of HBM, blocks are 128×128 to land on the MXU, and the
backward pass recomputes P from saved per-row logsumexp instead of storing
probabilities.

Layout: the public entry takes [B, S, H, D] (model layout) and transposes to
[B, H, S, D] so the trailing two block dims are (block_s, head_dim) — full
(sublane, lane) tiles. XLA fuses the transposes into neighbouring ops.

Grid convention: the innermost grid dimension is the contraction over KV (or
Q, in the dk/dv kernel) blocks; TPU grids execute sequentially so VMEM
scratch accumulators carry across it ("arbitrary" dimension semantics), and
outputs are flushed on the last inner step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512-blocks win on v5e at bench shapes (benchmarks/probe_flash.py at
# [8,1024,16,64], chip run PR 21: fwd 3.79ms @128 -> 1.00ms @512, grad
# 10.2 -> 2.91); VMEM for the [bq, bk] f32 score tile stays at 1MB.
# Module-level so benchmarks/mfu_sweep.py can tune without threading
# kwargs through every model layer.
DEFAULT_BLOCK = 512
_NEG_INF = -1e30


def _interpret() -> bool:
    """Interpret mode is chosen because the platform is cpu, never because
    the backend failed: a backend error propagates to the caller."""
    return jax.devices()[0].platform == "cpu"


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, nk, seq_len):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: skip blocks entirely in the future (first row of the q block
    # is above the last col of the k block).
    needed = True
    if causal:
        needed = (iq * bq + bq - 1) >= (ik * bk)

    @pl.when(needed)
    def _block():
        # Dots take the native bf16 operands (MXU full rate) and accumulate
        # in f32 via preferred_element_type; only the softmax statistics are
        # carried in f32. Casting inputs to f32 would drop the MXU to a
        # quarter of its bf16 rate.
        q = q_ref[0, 0]                       # [bq, D] bf16
        k = k_ref[0, 0]                       # [bk, D] bf16
        v = v_ref[0, 0]                       # [bk, D] bf16
        if seq_len % bk:
            # Padded kv rows hold uninitialized garbage (possibly NaN/inf);
            # a masked p of exactly 0 still yields 0*NaN=NaN in the dot.
            kv_valid = (ik * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0)) < seq_len
            k = jnp.where(kv_valid, k, jnp.zeros_like(k))
            v = jnp.where(kv_valid, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk] f32
        if causal or seq_len % bk:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            valid = cols < seq_len
            if causal:
                valid &= rows >= cols
            s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_scr[:]                     # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                # [bq, bk] f32
        alpha = jnp.exp(m_prev - m_new)       # [bq, 1]
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _flush():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:] + jnp.log(l_safe)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    """q: [B,H,S,D], k: [B,KVH,S,D], v: [B,KVH,S,Dv] -> (o [B,H,S,Dv],
    lse [B,H,S,1] f32). Dv may differ from D (latent attention: keys 192
    wide, values 128)."""
    B, H, S, D = q.shape
    KVH, Dv = k.shape[1], v.shape[-1]
    group = H // KVH
    bq = min(block_q, S)
    bk = min(block_k, S)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(S, bk)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk,
        seq_len=S)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
            # Trailing singleton keeps the (sublane, lane) block = (bq, 1),
            # which Mosaic accepts (lane == full array dim).
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, bq, bk, nk, seq_len):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = True
    if causal:
        needed = (iq * bq + bq - 1) >= (ik * bk)

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0]                       # bf16
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                   # [bq, 1] f32
        delta = delta_ref[0, 0]               # [bq, 1] f32
        if seq_len % bk:
            kv_valid = (ik * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bk, 1), 0)) < seq_len
            k = jnp.where(kv_valid, k, jnp.zeros_like(k))
            v = jnp.where(kv_valid, v, jnp.zeros_like(v))
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal or seq_len % bk:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            valid = cols < seq_len
            if causal:
                valid &= rows >= cols
            s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse)                  # [bq, bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _flush():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, bq, bk, nq, seq_len):
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = True
    if causal:
        needed = (iq * bq + bq - 1) >= (ik * bk)

    @pl.when(needed)
    def _block():
        q = q_ref[0, 0]                       # bf16
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                   # [bq, 1] f32
        delta = delta_ref[0, 0]               # [bq, 1] f32
        if seq_len % bq:
            q_valid = (iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, 1), 0)) < seq_len
            q = jnp.where(q_valid, q, jnp.zeros_like(q))
            do = jnp.where(q_valid, do, jnp.zeros_like(do))
            delta = jnp.where(q_valid, delta, 0.0)
        # s^T directly: [bk, bq]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        # Padded q rows carry garbage lse/delta — always mask rows >= S so
        # they cannot contribute to dk/dv of in-range kv rows.
        cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        valid = rows < seq_len
        if causal:
            valid &= rows >= cols
        st = jnp.where(valid, st, _NEG_INF)
        pt = jnp.exp(st - lse.T)              # [bk, bq] f32
        pt = jnp.where(valid, pt, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bk, bq]
        dst = pt * (dpt - delta.T) * scale
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _flush():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, block_q, block_k):
    q, k, v, o, lse = res
    lse = lse[..., None]  # [B, H, S, 1], the kernels' block shape
    do = g.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, H, S, 1]
    return flash_bwd_core(q, k, v, do, lse, delta, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k)


def flash_bwd_core(q, k, v, do, lse, delta, *, scale, causal,
                   block_q=DEFAULT_BLOCK, block_k=DEFAULT_BLOCK):
    """Backward kernels given externally supplied row stats.

    lse/delta are [B,H,S,1] and may come from a *global* softmax (ring
    attention merges chunk statistics before calling this per chunk) — p is
    recomputed as exp(s - lse), so partial-chunk gradients compose by
    simple accumulation.
    """
    B, H, S, D = q.shape
    KVH, Dv = k.shape[1], v.shape[-1]
    group = H // KVH
    bq = min(block_q, S)
    bk = min(block_k, S)
    nq = pl.cdiv(S, bq)
    nk = pl.cdiv(S, bk)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, seq_len=S),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j, g_=group: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, i, j, g_=group: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # dk/dv per *query* head, then segment-sum over the GQA group in XLA.
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, seq_len=S),
        grid=(B, H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, j, i, g_=group: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv),
                         lambda b, h, j, i, g_=group: (b, h // g_, j, 0)),
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    if group > 1:
        dk = dk_h.reshape(B, KVH, group, S, D).sum(axis=2).astype(k.dtype)
        dv = dv_h.reshape(B, KVH, group, S, Dv).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk, dv


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    return o


# The forward kernel's two outputs, named inside the forward rule so that a
# remat policy can keep them (`save_only_these_names(*RESIDUAL_NAMES)`): the
# backward rule reads exactly these arrays, and a pallas_call is not a dot,
# so a dots-only policy would run the kernel again to get them back.
RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    # Saved as [B,H,S], S on the lanes: a float32 [...,S,1] tiled (8,128)
    # as it stands stores 128 lanes for each one used (the v5e compiler
    # re-lays a saved stack S-minor by itself; this does not rest on that).
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, KVH, D]
    v: jax.Array,  # [B, S, KVH, Dv]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Flash attention in model layout [B, S, H, D] -> [B, S, H, Dv];
    differentiable. The values' width Dv may differ from D."""
    block_q = block_q or DEFAULT_BLOCK
    block_k = block_k or DEFAULT_BLOCK
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, S, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    ot = _flash(qt, kt, vt, scale, causal, block_q, block_k)
    return jnp.swapaxes(ot, 1, 2)
