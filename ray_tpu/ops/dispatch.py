"""Where a hand-written kernel runs: what every kernel family's rule shares.

A family's own `use_kernels(platform, <shapes>, on_mesh)` stays beside its
kernels, a pure function with the shape terms only it knows; this module is
the only code in `ray_tpu/ops` and `ray_tpu/models` that reads the platform
or the sharding context to choose, interpret or refuse a kernel. A dispatcher
is `s = dispatch.site()`, its rule, `dispatch.observe(...)`, the body.

family            its rule                     phase-table row     under a mesh
----------------  ---------------------------  ------------------  ----------------
flash             flash_attention.use_kernels  flash.plan (1)      `shard_map` (2)
kda               kda.use_kernels              kda.core.*          XLA body
gdn               kda.use_kernels              gdn.core.*          XLA body
mixer.conv        kda.use_conv_kernels         mixer.conv.*        XLA body
mixer.gated_norm  kda.use_norm_kernels         mixer.gated_norm.*  XLA body
ssd               ssd.use_kernels              ssd.core.*          XLA body
moe               moe.use_kernels              none (3)            `lax.ragged_dot`
dsa               none: Pallas everywhere      dsa.plan            refuses (4)
mamba1            selective_scan.use_kernels   mamba1.core.*       refuses (4)
shortconv         shortconv.use_kernels        shortconv.core.*    XLA body

A rule's own terms, beside `mosaic`: flash none (`tile_sizes` fits any
shape); kda and gdn (the scalar-decay call) keys and values of whole lane
tiles and a chunk of 128; mixer.conv channels whole, a norm over 128;
mixer.gated_norm channels whole, a group of whole tiles that divides them;
ssd chunk and state whole, heads that fill 128 lanes within a group, states
within `_STATE_BYTES`; moe 2-byte operands and widths whole; dsa on a TPU an S
of whole 4,096s (`sparse_attention`); mamba1 channels in blocks of 1,024, a
state of 128 at most, a chunk of whole sublanes; shortconv channels whole.
(1) and `flash.plan.bwd_*`, by the call itself, no suffix. (2) a batch /
head shard a device
(`attention._shard_mapped_attention`; ring or ulysses under a live `seq`
axis, on any platform). (3) `train.moe_*` are the step's counters. (4)
`one_chip`: from `_dsa_mixer`, and from `selective_scan`, whose rule
therefore takes no `on_mesh`.

`*` is `pallas` or `xla` (`observe`). On the CPU every Pallas body that runs
(a test that sets a rule true; "dsa" always) is interpreted (`interpret`).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax

from ray_tpu.parallel.sharding import current_sharding_ctx
from ray_tpu.util import tracing


class Site(NamedTuple):
    platform: str   # of the first device: "tpu", "cpu"
    on_mesh: bool   # under a sharding context of more than one device


def site() -> Site:
    """What a rule may observe beside its shapes. Decided from the live
    platform: a backend that fails to initialise raises here instead of
    quietly selecting an XLA body."""
    ctx = current_sharding_ctx()
    return Site(jax.devices()[0].platform,
                ctx is not None and ctx[0].size > 1)


def mosaic(platform: str, on_mesh: bool) -> bool:
    """A Mosaic call can run as it is: on a TPU, under no multi-device mesh
    (GSPMD cannot partition a Mosaic call: a hard lowering error on a real
    2x2 mesh, PR 21; it needs a `shard_map`, which only flash has)."""
    return platform == "tpu" and not on_mesh


def whole(*widths: int, of: int = 128) -> bool:
    """Every width is whole tiles of `of` (128: a vreg's lanes)."""
    return all(w % of == 0 for w in widths)


def interpret() -> bool:
    """`pallas_call(interpret=)`: chosen because the platform is cpu, never
    because the backend failed (a backend error propagates to the caller)."""
    return jax.devices()[0].platform == "cpu"


def observe(name: str, kernels: bool, **attrs) -> None:
    """One traced call's row in the phase table: `<name>.pallas` or
    `<name>.xla`, with what the call observed."""
    tracing.observe(name + (".pallas" if kernels else ".xla"), 0, slow=False,
                    **attrs)


def one_chip(what: str,
             roadmap: str = "R22 (a): the selection under a mesh") -> None:
    """Refuse a mesh. The "dsa" kernels are under no `shard_map` (a top-k
    across sequence shards is not written) and `shard_batch` would cut the
    three position streams as if they were rows of the batch; the "mamba1"
    scan is a Mosaic call on one device too, and what it would fall back
    to under a mesh is a loop over the tokens."""
    ctx = current_sharding_ctx()
    if ctx is not None and ctx[0].size > 1:
        raise NotImplementedError(
            f"{what} run on one chip, not under a mesh of {ctx[0].size} "
            f"(ROADMAP {roadmap})")


def residual_names() -> Tuple[str, ...]:
    """What every kernel family's forward rule names (`checkpoint_name`)
    for a remat policy to keep, so that a backward re-runs no kernel. A
    policy is by name: a name no operation of a layer carries changes
    nothing in its program."""
    from . import (flash_attention, kda, moe, selective_scan,
                   sparse_attention, ssd)

    return sum((m.RESIDUAL_NAMES for m in (
        flash_attention, kda, ssd, moe, sparse_attention, selective_scan)), ())
