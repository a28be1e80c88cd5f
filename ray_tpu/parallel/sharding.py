"""Logical-axis sharding rules: how params/activations map onto the mesh.

The reference delegates intra-model parallelism entirely to torch-ecosystem
libraries (SURVEY.md §5.7 — FSDP/DeepSpeed via Lightning strategies); here it
is a first-class library: every model tags its arrays with *logical* axis
names ("embed", "mlp", "heads", "batch", "seq", ...) and a rule table maps
logical axes → mesh axes per parallelism strategy. Changing strategy =
changing the rule table, never the model. This is the t5x/flax partitioning
idiom, which is the idiomatic TPU design (not a torch translation).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.util import tracing

# A logical spec is a tuple of logical axis names (or None), one per dim.
LogicalSpec = Tuple[Optional[str], ...]
Rules = Dict[str, Union[str, Tuple[str, ...], None]]

# Rule tables per strategy. Values name mesh axes (see mesh.AXIS_ORDER).
# "batch" always shards over (data, fsdp) — fsdp acts as extra DP for
# activations, the standard ZeRO-3 trick.
_BATCH = ("data", "fsdp")

RULES_DP: Rules = {"batch": _BATCH}

RULES_FSDP: Rules = {
    "batch": _BATCH,
    # Params: shard the largest dim over fsdp (all-gathered per layer under
    # jit; XLA overlaps the gather with compute).
    "embed": "fsdp",
    "vocab": "tensor",
    "mlp": "tensor",
    "heads": "tensor",
    "kv_heads": "tensor",
}

RULES_TP: Rules = {
    "batch": _BATCH,
    "layers": "pipe",  # layer stack split across pipeline stages
    "vocab": "tensor",
    "mlp": "tensor",
    "heads": "tensor",
    "kv_heads": "tensor",
    "embed": "fsdp",
    "seq_act": "seq",  # activation sequence dim under context parallelism
    "seq_res": "tensor",
    "expert": "expert",
}

DEFAULT_RULES = RULES_TP  # superset table; unused mesh axes are size-1


def logical_to_mesh_spec(logical: LogicalSpec, rules: Rules, mesh: Mesh) -> P:
    """Map a logical spec to a PartitionSpec, dropping axes the mesh doesn't
    have (or that have size 1 — avoids useless resharding)."""
    out = []
    used = set()
    for name in logical:
        if name is None:
            out.append(None)
            continue
        mapped = rules.get(name)
        if mapped is None:
            out.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        axes = tuple(
            a for a in axes if a in mesh.axis_names and mesh.shape[a] > 1 and a not in used
        )
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    # Trailing Nones can be dropped; keep them for clarity.
    return P(*out)


def named_sharding(mesh: Mesh, logical: LogicalSpec, rules: Optional[Rules] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_mesh_spec(logical, rules or DEFAULT_RULES, mesh))


def tree_shardings(mesh: Mesh, logical_tree: Any, rules: Optional[Rules] = None) -> Any:
    """Map a pytree of LogicalSpecs to a pytree of NamedShardings."""
    rules = rules or DEFAULT_RULES
    return jax.tree.map(
        lambda spec: named_sharding(mesh, spec, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )


def constrain(x: jax.Array, mesh: Mesh, logical: LogicalSpec, rules: Optional[Rules] = None):
    """with_sharding_constraint by logical names (t5x's logical constraint)."""
    return jax.lax.with_sharding_constraint(
        x, named_sharding(mesh, logical, rules)
    )


def shard_batch(mesh: Mesh, batch: Any) -> Any:
    """Place a host batch onto the mesh, sharded over the batch axes."""
    def put(x):
        x = np.asarray(x)
        if x.ndim == 0:
            return jax.device_put(x, replicated(mesh))
        spec: LogicalSpec = ("batch",) + (None,) * (x.ndim - 1)
        return jax.device_put(x, named_sharding(mesh, spec))

    with tracing.phase("train.shard_batch"):
        return jax.tree.map(put, batch)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --------------------------------------------------------- sharding context
# Models call maybe_constrain() on activations; it is a no-op unless a trainer
# established a (mesh, rules) context around tracing. This keeps model code
# mesh-agnostic (same function runs single-chip and on a v5p-64 FSDP mesh).

import contextlib
import threading

_ctx = threading.local()


@contextlib.contextmanager
def sharding_ctx(mesh: Mesh, rules: Optional[Rules] = None):
    prev = getattr(_ctx, "val", None)
    _ctx.val = (mesh, rules or DEFAULT_RULES)
    try:
        yield
    finally:
        _ctx.val = prev


def current_sharding_ctx() -> Optional[Tuple[Mesh, Rules]]:
    return getattr(_ctx, "val", None)


@contextlib.contextmanager
def no_sharding_ctx():
    """Suspend the context (inside shard_map bodies, where the mesh is fully
    manual and with_sharding_constraint would be ill-formed)."""
    prev = getattr(_ctx, "val", None)
    _ctx.val = None
    try:
        yield
    finally:
        _ctx.val = prev


def maybe_constrain(x: jax.Array, logical: LogicalSpec) -> jax.Array:
    ctx = current_sharding_ctx()
    if ctx is None:
        return x
    mesh, rules = ctx
    return constrain(x, mesh, logical, rules)
