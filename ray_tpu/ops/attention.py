"""Attention ops: reference XLA implementation + dispatch point for Pallas.

The reference framework has no attention kernels at all (it delegates to
torch); here attention is a first-class op because it dominates the MFU
budget. `attention()` is the single entry point models call; it dispatches to
a Pallas flash kernel on TPU (ops.flash_attention) when shapes allow, else to
a fused-softmax XLA implementation that the compiler maps onto MXU+VPU well.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def reference_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, KVH, D]
    v: jax.Array,  # [B, S, KVH, Dv]
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain XLA attention with GQA head-broadcast. Computes in f32 for
    numerical stability, returns q.dtype, [B, S, H, Dv]. `window` (with
    `causal`): query i sees keys j with 0 <= i - j < window."""
    B, S, H, D = q.shape
    KVH = k.shape[2]
    assert H % KVH == 0, f"heads {H} not divisible by kv_heads {KVH}"
    group = H // KVH
    scale = scale if scale is not None else D ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # [B, KVH, group, S, D] x [B, KVH, S, D] -> [B, KVH, group, S, S]
    qg = qf.reshape(B, S, KVH, group, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, kf)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), dtype=bool))
        if window is not None:
            mask = mask & ~jnp.tril(mask, -window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, vf)
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def _shard_mapped_attention(q, k, v, causal, scale, kernels, window=None):
    """Run attention per shard of a multi-device mesh via shard_map: pjit
    keeps global array semantics outside; inside, each device works on its
    batch/head/sequence shard.

    GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned", a hard lowering error on a real 2x2 mesh —
    chip run PR 21), and attention is independent per batch row and head, so
    with the sequence whole on every device the flash kernel runs on the
    local batch/head shard with no communication. A nontrivial `seq` axis
    that the rules route the activation sequence dim onto adds context
    parallelism, in two schemes (SURVEY §5.7): ring (K/V rotation — any head
    count) and ulysses (all-to-all head scattering — fewer collectives when
    the head counts divide the axis). RTPU_SP_MODE selects: ring | ulysses |
    auto (ulysses when divisible, else ring).

    Returns None when neither applies: dense attention under pjit
    partitions itself. A `window` goes to the flash kernel of each shard;
    the two sequence-parallel schemes refuse it (their chunks would have to
    know where the band lies in the whole sequence)."""
    from jax import shard_map

    from ray_tpu import flags
    from ray_tpu.parallel.sharding import (current_sharding_ctx,
                                           logical_to_mesh_spec)
    from .flash_attention import flash_attention
    from .ring_attention import ring_attention
    from .ulysses_attention import ulysses_attention

    mesh, rules = current_sharding_ctx()
    q_spec = logical_to_mesh_spec(("batch", "seq_act", "heads", None), rules, mesh)
    kv_spec = logical_to_mesh_spec(("batch", "seq_act", "kv_heads", None), rules, mesh)

    def run(body):
        return shard_map(body, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)

    if q_spec[1] != "seq":
        # The sequence dim is whole on every device: no seq axis, or rules
        # that don't route the activation sequence dim onto it (e.g.
        # RULES_DP on a mesh that happens to have seq>1 — a ring over
        # replicated full-sequence "chunks" would silently double-count
        # keys).
        if not kernels:
            return None
        return run(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, scale=scale, window=window))
    if window is not None:
        raise NotImplementedError(
            "windowed attention over a sharded sequence: ring_attention and "
            "ulysses_attention take no window")
    mode = flags.get("RTPU_SP_MODE")
    sp = mesh.shape["seq"]

    def _extent(entry) -> int:
        if entry is None:
            return 1
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    # Divisibility is a PER-DEVICE property: the head dim may additionally
    # be tensor-sharded by the in_specs, so the local head count inside
    # shard_map is global // extent(head axes).
    h_local = q.shape[2] // _extent(q_spec[2])
    kvh_local = k.shape[2] // _extent(kv_spec[2])
    divisible = h_local % sp == 0 and kvh_local % sp == 0
    if mode in ("ulysses", "auto") and divisible:
        body = lambda q, k, v: ulysses_attention(
            q, k, v, "seq", causal=causal, scale=scale)
    else:
        # Ring handles any head count; an explicit ulysses ask that cannot
        # divide falls back here rather than failing the whole step.
        body = lambda q, k, v: ring_attention(
            q, k, v, "seq", causal=causal, scale=scale)
    return run(body)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Dispatching attention entry point used by all models: the flash
    kernels where `flash_attention.use_kernels` says so, under a
    multi-device mesh inside a `shard_map`, else `reference_attention`.
    `window` (with `causal`): query i sees keys j with 0 <= i - j <
    window."""
    from . import dispatch, flash_attention as fa

    s = dispatch.site()
    kernels = fa.use_kernels(s.platform)
    if s.on_mesh:
        out = _shard_mapped_attention(q, k, v, causal, scale, kernels, window)
        if out is not None:
            return out
    if kernels:
        return fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                  window=window)
    return reference_attention(q, k, v, causal=causal, scale=scale,
                               window=window)
