"""Sharded training-step construction: params+optimizer+batch → one jitted
XLA program over a mesh.

This is the layer where the reference's per-step torch/NCCL machinery
(DDP all-reduce inside the user train loop, SURVEY.md §3.4.4-6) collapses into
compiler output: gradients reduce over `data`, parameters gather/scatter over
`fsdp`, activations split over `tensor`/`seq` — all emitted by GSPMD from the
shardings we pin on params, optimizer state and batch: a moment is pinned
to its parameter's sharding (`ShardedTrainStep._opt_shardings`).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel import sharding as shd
from ray_tpu.util import tracing


# Where the loop's thread spends a step's period by the program's own phases,
# as a `train.stall` record's attributes; the rest is the caller's.
_BEAT_PARTS = {"shard_ms": "train.shard_batch", "enqueue_ms": "train.step",
               "report_ms": "train.report"}


class ShardedTrainStep:
    """Holds the jitted init/step pair and the shardings they pin.

    loss_fn(params, batch) -> scalar loss. `logical_specs` is the pytree of
    logical axis names matching params (models expose param_logical_specs).
    `has_aux`: loss_fn returns (loss, aux), aux a pytree of device values
    (counters); `step` then returns (params, opt_state, loss, aux). Nothing
    is called back to the host from inside the program: the caller reads
    aux when it waits for the loss (`observe_counters`).
    """

    def __init__(
        self,
        *,
        init_params_fn: Callable[[jax.Array], Any],
        loss_fn: Callable[[Any, Any], jax.Array],
        logical_specs: Any,
        mesh: Mesh,
        rules: Optional[shd.Rules] = None,
        optimizer: Optional[optax.GradientTransformation] = None,
        donate: bool = True,
        has_aux: bool = False,
    ):
        self.mesh = mesh
        self.rules = rules or shd.DEFAULT_RULES
        self.optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.0)
        self.param_shardings = shd.tree_shardings(mesh, logical_specs, self.rules)
        self._loss_fn = loss_fn
        self._init_params_fn = init_params_fn

        self._jit_init = None  # built by the first `init`

        def _step(params, opt_state, batch):
            with shd.sharding_ctx(self.mesh, self.rules):
                loss, grads = jax.value_and_grad(
                    self._loss_fn, has_aux=has_aux)(params, batch)
                updates, opt_state = self.optimizer.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            if has_aux:
                loss, aux = loss
                return params, opt_state, loss, aux
            return params, opt_state, loss

        self._jit_step = jax.jit(_step, donate_argnums=(0, 1) if donate else ())
        self._compiled_step = None  # see compile_step

        def _eval(params, batch):
            with shd.sharding_ctx(self.mesh, self.rules):
                return self._loss_fn(params, batch)

        self._jit_eval = jax.jit(_eval)

    def _opt_shardings(self):
        """The optimizer state's shardings: every copy of the parameters'
        tree in it (AdamW's mu and nu) is sharded as the parameters are, the
        rest (step counts) replicated. Left to propagation (`zeros_like`
        carries no data dependence for the partitioner to follow) the
        moments come out replicated: twice the model on every chip."""
        params = jax.eval_shape(self._init_params_fn, jax.random.key(0))
        replicated = shd.replicated(self.mesh)
        return optax.tree_map_params(
            self.optimizer, lambda _, sharding: sharding,
            jax.eval_shape(self.optimizer.init, params), self.param_shardings,
            transform_non_params=lambda _: replicated)

    def init(self, rng: jax.Array) -> Tuple[Any, Any]:
        if self._jit_init is None:
            def _init(rng):
                with shd.sharding_ctx(self.mesh, self.rules):
                    params = self._init_params_fn(rng)
                    return params, self.optimizer.init(params)

            self._jit_init = jax.jit(_init, out_shardings=(
                self.param_shardings, self._opt_shardings()))
        return self._jit_init(rng)

    def shard_batch(self, batch: Any) -> Any:
        return shd.shard_batch(self.mesh, batch)

    def step(self, params, opt_state, batch) -> Tuple[Any, ...]:
        tracing.beat("train", _BEAT_PARTS)  # a late step: `train.stall`
        with tracing.phase("train.step"):  # the host side: the enqueue
            return (self._compiled_step or self._jit_step)(
                params, opt_state, batch)

    def compile_step(self, params, opt_state, batch):
        """Compile the step ahead of time for these shapes and shardings;
        `step` then runs that executable (it accepts no other shapes). One
        compile serves the loop and `.memory_analysis()`, where
        `lower_step(...).compile()` beside a jitted call compiles twice."""
        self._compiled_step = self.lower_step(
            params, opt_state, batch).compile()
        return self._compiled_step

    @staticmethod
    def observe_counters(aux: Dict[str, Any]) -> Dict[str, float]:
        """A step's counters (has_aux) as floats, folded into the phase table
        as `train.<name>` (`util/tracing.observe`, the count in place of
        nanoseconds; no entry in the slow ring). Call it where the loop waits for the loss anyway:
        reading them blocks until the step is done."""
        out = {k: float(v) for k, v in aux.items()}
        for k, v in out.items():
            tracing.observe("train." + k, round(v), slow=False)
        return out

    def eval_loss(self, params, batch) -> jax.Array:
        return self._jit_eval(params, batch)

    def lower_step(self, params, opt_state, batch):
        """Expose the lowered/compiled step (for compile checks and AOT)."""
        return self._jit_step.lower(params, opt_state, batch)


def transformer_train_step(
    cfg,
    mesh: Mesh,
    *,
    rules: Optional[shd.Rules] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    pipeline_microbatches: Optional[int] = None,
    shift_inputs: bool = False,
    with_counters: bool = False,
) -> ShardedTrainStep:
    """Convenience: wire a models.transformer config into a ShardedTrainStep.

    When the mesh has pipe>1, the decoder runs as an in-graph GPipe pipeline
    (parallel/pipeline.py) with `pipeline_microbatches` microbatches
    (default: 2x the stage count, a reasonable bubble/memory tradeoff).
    ``shift_inputs`` selects the [B,S+1]-tokens convention (models.
    transformer.loss_fn docstring) — the high-throughput path.
    ``with_counters``: the step also returns the model's routing counters
    (ShardedTrainStep `has_aux`)."""
    from ray_tpu.models import transformer as tfm

    if "pipe" in mesh.axis_names and mesh.shape["pipe"] > 1:
        if getattr(cfg, "fused_ce", False):
            # The pipelined loss computes logits inside the last stage
            # (parallel/pipeline.py) and would silently skip the fused
            # epilogue; fail loudly rather than drop the memory win the
            # flag promises.
            raise NotImplementedError(
                "fused_ce is not supported under pipeline parallelism "
                "yet — unset cfg.fused_ce for pipe>1 meshes")
        if with_counters:
            raise NotImplementedError(
                "the pipelined loss threads GShard's aux loss through its "
                "schedule and no routing counters")
        from ray_tpu.parallel.pipeline import pipeline_loss_fn

        M = pipeline_microbatches or 2 * mesh.shape["pipe"]
        loss = pipeline_loss_fn(
            cfg, mesh, rules=rules or shd.DEFAULT_RULES, num_microbatches=M,
            shift_inputs=shift_inputs)
    else:
        loss = lambda params, batch: tfm.loss_fn(
            params, batch, cfg, shift_inputs=shift_inputs,
            with_counters=with_counters)

    return ShardedTrainStep(
        init_params_fn=lambda rng: tfm.init_params(rng, cfg),
        loss_fn=loss,
        logical_specs=tfm.param_logical_specs(cfg),
        mesh=mesh,
        rules=rules,
        optimizer=optimizer,
        has_aux=with_counters,
    )
