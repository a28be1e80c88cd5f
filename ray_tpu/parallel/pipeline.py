"""Pipeline parallelism over the `pipe` mesh axis: a microbatched GPipe
schedule inside ONE jitted step, expressed entirely in GSPMD auto mode.

SURVEY.md §5.7 names pipeline parallelism a first-class requirement; the
reference has no in-graph pipeline engine at all (its compiled-DAG pipelines
actors at the task layer, dag/compiled_dag_node.py:291 — a different
altitude). The TPU-native design (the MaxText/praxis idiom) runs the whole
schedule inside XLA with NO manual collectives:

- The layer stack [L, ...] reshapes to [P, L/P, ...] with the leading stage
  dim sharded over `pipe` — each device holds its stage's contiguous layer
  block, zero repartitioning.
- A state buffer [P, mb, S, d], also pipe-sharded on the stage dim, holds
  the microbatch each stage is processing. Every tick vmaps the stage body
  (a lax.scan over that stage's layers) across the stage dim — perfectly
  SPMD — then hands activations to the next stage with jnp.roll along the
  stage dim, which XLA lowers to a CollectivePermute over `pipe`.
- Because everything is ordinary sharded computation, tensor/fsdp/expert
  sharding INSIDE a stage needs nothing special: the same rule table that
  shards the unpipelined model shards each stage's params and activations,
  and GSPMD inserts the per-stage collectives. pipe x fsdp, pipe x tensor
  and MoE-under-pipe compose by construction; autodiff is the standard
  transpose (the roll transposes to the reverse roll — the backward
  pipeline for free).
- The schedule is GPipe: with M microbatches and P stages it runs M+P-1
  ticks; bubble ticks compute garbage that output masking discards, and
  the MoE aux-loss contribution of bubbles is masked out the same way.

Embedding and the LM head run outside the scan in ordinary GSPMD land, so
vocab/fsdp sharding of those params keeps working unchanged.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel import sharding as shd


def _stage_spec(rules: shd.Rules, mesh: Mesh, logical: Tuple) -> P:
    """PartitionSpec for an array with a leading stage dim: ('pipe', then
    the usual logical mapping for the remaining dims)."""
    inner = shd.logical_to_mesh_spec(logical, rules, mesh)
    return P("pipe", *tuple(inner))


def pipeline_apply(
    cfg,
    layers: Dict[str, jax.Array],
    x: jax.Array,  # [M, mb, S, d] microbatched activations
    mesh: Mesh,
    rules: Optional[Dict] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the layer stack as a P-stage GPipe pipeline.

    Returns (activations [M, mb, S, d], summed MoE aux loss — zero for
    dense stacks)."""
    from ray_tpu.models.transformer import (layer_scan_body, one_kind_stack,
                                            param_logical_specs)

    rules = rules or shd.DEFAULT_RULES
    num_stages = mesh.shape["pipe"]
    M, mb, S, d = x.shape
    num_ticks = M + num_stages - 1

    # [L, ...] -> [P, L/P, ...], stage dim pinned to `pipe`; remaining dims
    # keep their logical sharding (fsdp/tensor/expert) from the rule table.
    kind, lspecs = one_kind_stack(param_logical_specs(cfg), cfg,
                                  "pipeline parallelism")

    def stage_fold(a, spec):
        L = a.shape[0]
        if L % num_stages:
            raise ValueError(
                f"n_layers {L} not divisible by pipe={num_stages}")
        staged = a.reshape(num_stages, L // num_stages, *a.shape[1:])
        # [P, L/P, *param_dims]: pipe on the stage dim, None for the L/P
        # dim, then the per-param logical mapping — off-by-one here would
        # silently shard heads/mlp dims onto the wrong mesh axes.
        inner = shd.logical_to_mesh_spec(tuple(spec)[1:], rules, mesh)
        return jax.lax.with_sharding_constraint(
            staged, NamedSharding(mesh, P("pipe", None, *tuple(inner))))

    layers_staged = jax.tree.map(
        stage_fold, layers, lspecs,
        is_leaf=lambda v: not isinstance(v, dict))

    act_logical = ("batch", "seq_act", "embed")
    state_sharding = NamedSharding(mesh, _stage_spec(rules, mesh, act_logical))

    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (mb, S))
    scan_body = layer_scan_body(cfg, kind, positions)
    # Ring attention is a shard_map over `seq` and cannot nest inside the
    # vmapped stage body; dropping the seq_act routing makes attention()
    # use the dense per-stage kernel (context parallelism composes with
    # pipe at the batch level instead). Without `seq_res` the stage's
    # residual keeps its rows whole over `tensor` too (the decomposed
    # products of parallel/tensor_overlap.py are `shard_map`s as well).
    inner_rules = {k: v for k, v in rules.items()
                   if k not in ("seq_act", "seq_res")}

    def stage_apply(stage_layers, h):
        with shd.sharding_ctx(mesh, inner_rules):
            out, extras = lax.scan(scan_body, h, stage_layers)
        return out, extras["aux"].sum() if "aux" in extras else jnp.zeros(())

    vapply = jax.vmap(stage_apply)

    state0 = jnp.zeros((num_stages, mb, S, d), x.dtype)
    outputs0 = jnp.zeros_like(x)
    stage_ids = jnp.arange(num_stages)

    def tick(carry, t):
        state, outputs, aux_acc = carry
        # Stage 0 picks up microbatch t (bubble ticks recirculate garbage
        # that the masks below ignore).
        inject = x[jnp.minimum(t, M - 1)]
        state = state.at[0].set(jnp.where(t < M, inject, state[0]))
        state = jax.lax.with_sharding_constraint(state, state_sharding)
        out, aux = vapply(layers_staged, state)  # [P, mb, S, d], [P]
        # Stage s processes microbatch (t - s) this tick; outside [0, M)
        # it's a bubble — mask its aux contribution.
        mb_idx = t - stage_ids
        valid = (mb_idx >= 0) & (mb_idx < M)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0).sum()
        # The last stage emits microbatch t-(P-1) once real work reaches it.
        out_idx = t - (num_stages - 1)
        idx = jnp.clip(out_idx, 0, M - 1)
        outputs = outputs.at[idx].set(
            jnp.where(out_idx >= 0, out[num_stages - 1], outputs[idx]))
        # Hand activations to the next stage: a roll on the pipe-sharded
        # stage dim = CollectivePermute over ICI. Slot 0's content is
        # overwritten by the next injection.
        state = jnp.roll(out, 1, axis=0)
        state = jax.lax.with_sharding_constraint(state, state_sharding)
        return (state, outputs, aux_acc), None

    (_, outputs, aux_acc), _ = lax.scan(
        tick, (state0, outputs0, jnp.zeros((), jnp.float32)),
        jnp.arange(num_ticks))
    # The per-layer aux loss is a token-MEAN (ops/moe.py); every microbatch
    # contributes one mean per layer, so the accumulated sum is M x the
    # full-batch value — normalize to match the unpipelined loss exactly
    # (equal-size microbatches make mean-of-means = full mean).
    return outputs, aux_acc / M


def pipeline_loss_fn(cfg, mesh: Mesh, *, rules=None, num_microbatches: int = 4,
                     shift_inputs: bool = False):
    """Build loss_fn(params, batch) running the decoder as a GPipe pipeline.

    Drop-in for models.transformer.loss_fn wherever the mesh has pipe>1;
    wire into ShardedTrainStep via train.step.transformer_train_step(...,
    pipeline_microbatches=M). ``shift_inputs`` selects the [B,S+1]-tokens
    convention (models.transformer.loss_fn docstring). MoE stacks thread
    their load-balancing aux loss through the schedule (bubble ticks
    masked out).
    """
    from ray_tpu.models import transformer as tfm

    rules = rules or shd.DEFAULT_RULES
    # The embedding and the head on the stages' layout: rows whole over
    # `tensor` (see `pipeline_apply`).
    rules = {k: v for k, v in rules.items() if k != "seq_res"}
    M = num_microbatches
    # Refuse another stack here, not at trace time.
    tfm.one_kind_stack(tfm.param_logical_specs(cfg), cfg,
                       "pipeline parallelism")
    if cfg.loop_steps > 1:
        raise NotImplementedError(
            "the pipeline's schedule visits a stage once a microbatch: a "
            "looped stack (loop_steps > 1) needs a stage visited once a pass, "
            "the last stage's output sent back to the first, and a head a "
            "pass; it trains unpipelined")

    def loss_fn(params, batch):
        with shd.sharding_ctx(mesh, rules):
            return _loss(params, batch)

    def _loss(params, batch):
        tokens = batch["tokens"]
        inputs = tokens[:, :-1] if shift_inputs else tokens
        B, S = inputs.shape
        if B % M != 0:
            raise ValueError(
                f"batch {B} not divisible by num_microbatches {M}")
        x = tfm.embed_tokens(params, inputs, cfg)  # [B, S, d]
        x = x.reshape(M, B // M, S, -1)
        _, layers = tfm.one_kind_stack(params, cfg, "pipeline parallelism")
        y, aux = pipeline_apply(cfg, layers, x, mesh, rules)
        y = y.reshape(B, S, -1)
        y = shd.maybe_constrain(y, ("batch", "seq_act", "embed"))
        logits = tfm.lm_head(params, y, cfg)
        if shift_inputs:
            targets, valid = tfm.shift_targets_valid(
                tokens, batch.get("mask"))
            loss = tfm.token_cross_entropy(logits, targets, valid)
        else:
            loss = tfm.next_token_loss(logits, batch)
        if cfg.moe_num_experts:
            loss = loss + cfg.moe_aux_coef * aux
        return loss

    return loss_fn
