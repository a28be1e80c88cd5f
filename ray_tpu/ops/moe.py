"""Mixture-of-Experts blocks. Two routings, two paths:

- `moe_ffn` (GShard / Switch, the rest of this docstring): softmax gates,
  top-k renormalised, a one-hot [T, E, C] dispatch with a capacity a group;
  tokens over capacity are DROPPED; every expert is held; a load-balancing
  aux loss. `TransformerConfig.moe_router = "softmax_capacity"`.
- `moe_ffn_held`: NO token dropped, no aux loss, the routing an argument:
  `sigmoid_route` (the DeepSeek-V3 / Kimi family: sigmoid scores, top-k of
  score + correction bias (selection only), weights renormalised and scaled)
  or `softmax_route` (softmax over every expert's score, top-k of the
  probabilities, renormalised). The layer is told which contiguous range of
  the experts it holds (`held`): it routes over all of them, sorts the
  assignments by expert with its own first, runs those as grouped matrix
  products (`lax.ragged_dot`, no one-hot) in one window of a margin over the held
  experts' even share of the rows and no fewer than a row a token (smaller
  ones for what a skewed routing puts past it), every pass round the products
  over the row blocks the routing filled, and returns its own experts' part. With every expert held
  and the expert dim sharded over an `expert` mesh axis this is expert
  parallelism.
  `TransformerConfig.moe_router = "sigmoid"` or `"softmax"`.

GShard-style capacity-based top-k dispatch:

SURVEY.md §5.7 lists MoE/expert parallelism as a first-class requirement;
the reference has no MoE kernels (torch territory). The TPU-native design is
the GShard/Switch einsum formulation: routing produces one-hot dispatch and
weighted combine tensors, tokens move into per-expert buffers with a single
einsum, the expert FFNs run as ONE batched matmul over the expert dim, and
a second einsum combines results. Sharding the expert dim over the `expert`
mesh axis turns those einsums into all-to-alls emitted by GSPMD — exactly
the layout the scaling-book recipe prescribes (no hand-written collectives).

Over-capacity tokens are dropped (their combine weight is zero and the
residual connection carries them through unchanged) — standard
capacity-factor semantics.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from . import dispatch


def moe_ffn(
    x: jax.Array,          # [B, S, d] (cfg.dtype)
    router_w: jax.Array,   # [d, E]
    w_gate_up: jax.Array,  # [E, d, 2, F]
    w_down: jax.Array,     # [E, F, d]
    *,
    experts_per_token: int = 2,
    capacity_factor: float = 1.25,
    group_size: int = 4096,
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output [B, S, d], aux load-balancing loss scalar).

    Tokens route within fixed-size GROUPS (GShard's grouping): dispatch
    memory is O(groups * g * C) with C = O(k*g/E) — linear in total tokens —
    instead of the quadratic O(T * k*T/E) of ungrouped routing.
    """
    B, S, d = x.shape
    tokens = B * S
    # Largest power-of-two divisor of T up to group_size keeps shapes exact.
    g = 1
    while g * 2 <= min(group_size, tokens) and tokens % (g * 2) == 0:
        g *= 2
    xg = x.reshape(tokens // g, g, d)

    def per_group(xf):
        return _moe_group(
            xf, router_w, w_gate_up, w_down,
            experts_per_token=experts_per_token,
            capacity_factor=capacity_factor, dtype=dtype)

    out, aux = jax.vmap(per_group)(xg)
    return out.reshape(B, S, d), aux.mean()


def _moe_group(
    xf: jax.Array,         # [T, d] one routing group
    router_w: jax.Array,
    w_gate_up: jax.Array,
    w_down: jax.Array,
    *,
    experts_per_token: int,
    capacity_factor: float,
    dtype,
) -> Tuple[jax.Array, jax.Array]:
    tokens, d = xf.shape
    E = router_w.shape[-1]
    k = experts_per_token
    capacity = max(1, int(capacity_factor * tokens * k / E))

    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [T,E]
    probs = jax.nn.softmax(logits, axis=-1)

    # Top-k expert choice per token.
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # [T, k]
    # Renormalize the chosen gates (Mixtral/GShard convention).
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    # Position of each (token, choice) within its expert's buffer: cumsum
    # over the one-hot assignment, choices flattened in priority order so
    # k=0 assignments win buffer slots before k=1.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # [T, k, E]
    flat = onehot.transpose(1, 0, 2).reshape(k * tokens, E)  # [k*T, E]
    pos_flat = jnp.cumsum(flat, axis=0) - flat               # [k*T, E]
    pos = pos_flat.reshape(k, tokens, E).transpose(1, 0, 2)  # [T, k, E]
    position = (pos * onehot).sum(-1)                        # [T, k]
    keep = position < capacity                               # [T, k]

    # Dispatch/combine tensors [T, k] -> [T, E, C].
    cap_onehot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)
    disp = (onehot.astype(jnp.float32)[..., None]
            * cap_onehot[:, :, None, :]
            * keep[..., None, None])                         # [T, k, E, C]
    combine = (disp * gate_vals[..., None, None]).sum(1)     # [T, E, C]

    # Route tokens to expert buffers: [E, C, d].
    expert_in = jnp.einsum(
        "tec,td->ecd", disp.sum(1).astype(dtype), xf.astype(dtype))
    # Batched expert FFN (swiglu), ONE einsum per projection over E.
    gu = jnp.einsum("ecd,edgf->ecgf", expert_in, w_gate_up.astype(dtype))
    act = jax.nn.silu(gu[:, :, 0]) * gu[:, :, 1]             # [E, C, F]
    expert_out = jnp.einsum("ecf,efd->ecd", act, w_down.astype(dtype))
    out = jnp.einsum(
        "tec,ecd->td", combine.astype(dtype), expert_out)    # [T, d]

    # Load-balancing aux loss (Switch: E * mean(frac_tokens * frac_probs)).
    assigned = onehot[:, 0].astype(jnp.float32)              # top-1 [T, E]
    frac_tokens = assigned.mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)

    return out, aux


def sigmoid_route(x: jax.Array, router_w: jax.Array, bias: jax.Array, *,
                  experts_per_token: int, routed_scale: float):
    """Scores over ALL experts for tokens x [T, d]: s = sigmoid(x W_r); the
    top k of s + bias are selected (the bias takes part in the selection
    only and gets no gradient); weights s_sel / sum(s_sel) * routed_scale.
    float32 at full precision: a rounding here flips which expert is 8th.
    -> (expert ids [T, k] int32, weights [T, k] f32)."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(
        s + jax.lax.stop_gradient(bias.astype(jnp.float32)),
        experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * routed_scale
    return idx.astype(jnp.int32), w


def softmax_route(x: jax.Array, router_w: jax.Array, *,
                  experts_per_token: int):
    """Scores over ALL experts for tokens x [T, d]: p = softmax(x W_r); the
    top k of p are selected; weights p_sel / sum(p_sel). float32 at full
    precision, as `sigmoid_route`.
    -> (expert ids [T, k] int32, weights [T, k] f32)."""
    p = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    w, idx = jax.lax.top_k(p, experts_per_token)
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


# The first window of gathered assignments is the held experts' even share of
# them (tokens x k x held / experts) times this margin. A window spans the
# sorted rows of ALL held experts, so one expert's skew never matters: only
# the held total does; until PR 43 the layer's time followed the window's
# rows, not the held ones (the sweeps below are of that body), and every
# further trip of the backward loop writes and adds a whole dW1, dW2 and dx,
# so ONE trip is the rule. Sweep on the chip
# (`benchmarks/probe_moe.py`, PR 34; one layer alone, forward + backward, ms
# at mellum2's [16384, 2304] with 16 of 64 experts of 896 held / at the
# hybrid's [8192, 2304] with 8 of 256 of 1024; 32,819 / 2,166 held rows, an
# even routing: 0.99-1.08 of the even share on the chip at a run's start):
#   4.0 (every assignment, PRs 27-33)   64.92 / 11.56
#   1.5                                 32.82 /  9.91
#   1.25                                30.36 /  9.82
#   1.125                               29.03 /  9.32
#   0.5, then 3 x half of it            56.49 / 13.48
#   0.25, then 7 x half of it           80.87 / 17.17
# The margin is what the held total reaches in the steps a program runs, not
# what an even routing needs: in mellum2's cell AdamW pulls the router to the
# held experts (131 k -> 189-236 k assignments a step in 66-74 steps, two of
# the four layers far ahead of the others), a trip past the window costs a
# layer 10-22 ms, and how many a run takes is the seed's to decide. The cell,
# tokens/s/chip over 5-8 seeds, the middle half of them, rows past the window
# a run (parent 25,984; the driver's bound on the spread 260):
#   1.25   39,833-40,332   273-309   415-658 k
#   1.5    39,267-39,612   320       200-388 k
#   2.5    35,806-35,840   25        0          (66 steps)
#   3.0    does not fit the chip (the kept products: 187 MB over)
# (In the traced step the held experts take 38.0 ms a layer at 2.5 and 24.2
# at 1.25.) With a router that stays even (a balancing term in the cell's job
# file, PERF.md section 7 (d)) 1.25 is the value.
# What a margin costs since PR 43: memory (the [W, .] buffers), and where a
# sum back to the tokens is a scatter-add (`scatter_rows`), the sort and
# permutation of the unfilled rows it is given (half the window where the
# held rows end there); the grouped product that sums a first window on the
# chip since PR 50 (`block_sums`) visits no tile past the held rows. Every
# other pass works the blocks the held rows reach into (`block_rows`), so the
# layer's time follows the held total: same probe, `rows`, mellum2's shape at a held total of 0.5 /
# 1.0 / 1.5 / 2.0 / 2.5 of the even share, ms: 27.71 / 33.40 / 45.05 / 50.72
# / 56.41 where the whole-window passes took 47.89 / 51.24 / 54.58 / 57.91 /
# 61.29; the cell at 2.5: 35,816-35,839 -> 39,670-39,945 tokens/s/chip over
# six seeds (a faster run takes more steps, so its router drifts further
# and its late steps take further trips that the slower run never reached).
HELD_WINDOW_FACTOR = 2.5
# And the window is never under this many rows a token. A token picks an
# expert once, so the tokens are the rows ONE held expert can be given, and
# where few experts of many are held (the hybrid: 8 of 256, k = 8) the even
# share is a quarter of that: its held total read 0.55 to 1.63 of the even
# share over a run's steps and seeds, one expert alone up to 1.6 of it
# (`load_max` 3,291 for an even share of 2,048), so a window of 1.25 took a
# further trip in most steps and a number of them that the seed decides:
# 1.74 ms a layer saved, the cell's six runs 1.6% apart where the parent's
# were 0.3% (PERF.md section 6, PR 34, second round). At a row a token the
# hybrid's window is the 8,192 rows of PRs 27-33, which no step has passed
# (seven seeds 25,717-25,784 tokens/s/chip, the parent 25,195-25,219);
# mellum2's (81,920 for 16,384 tokens) is the margin's.
HELD_WINDOW_MIN_TOKENS = 1.0
# What falls past the first window is worked in windows of this share of it,
# so that a step that overflows by a few rows (a router drifting towards the
# held experts does, PERF.md section 6, PR 34) pays half a window and not a
# whole one more, while a trip's own cost (6 ms at mellum2's shape, 1.5 at
# the hybrid's) keeps the windows few. Same probe, `skew` (`lax.ragged_dot`
# products), ms at a share of 1 / 0.5 / 0.25 / 0.125: mellum2 with 46,510
# held rows 74.7 / 66.0 / 62.9 / 68.0, with 61,220 85.7 / 76.0 / 81.7 / 91.3;
# the hybrid with 3,534 14.6 / 14.2 / 14.3 / 15.1, with 9,087 25.1 / 33.9 /
# 32.8 / 38.1.
FURTHER_WINDOW_SHARE = 0.5

# The first window's grouped products' outputs (gate/up [W, 2F], down [W, d])
# carry the first two names in the forward rule, so that a remat policy can
# keep them (`save_only_these_names(*RESIDUAL_NAMES)`, models/transformer.py
# `layer_scan_body`, under either policy): the backward then gathers no rows
# and runs no product a second time. The third is the window's rows in token
# order and their tokens (two [W] int32, `token_order`), made where the
# window's sums are the grouped product: the backward's dx takes its rows
# through the same order; since PR 59 also the routing the windows go by
# (the sorted list, the runs' ends, the assignments' weights), so that the
# backward works the rows the forward filled.
RESIDUAL_NAMES = ("moe_gate_up", "moe_down", "moe_token_order")


def use_kernels(platform: str, dtype, widths: Tuple[int, ...],
                on_mesh: bool) -> bool:
    """The grouped products' dispatch rule, a pure function of what the code
    observes: the Pallas grouped matmul (`jax.experimental.pallas.ops.tpu.
    megablox`) where a Mosaic call can run (`dispatch.mosaic`) with 2-byte
    operands and widths of whole 128-lane tiles, else `lax.ragged_dot`,
    which XLA lowers to a Mosaic call of its own tiling: at a window of
    40,960 rows x 2,304 -> 1,792 that one takes 4.12 ms, 4.59 against the
    weights transposed and 5.10 for the weights' cotangent, the kernel at
    `_tiles` 1.98, 2.01 and 2.00 (`probe_moe.py parts`, PERF.md section 6,
    PR 34)."""
    return (dispatch.mosaic(platform, on_mesh)
            and jnp.dtype(dtype).itemsize == 2 and dispatch.whole(*widths))


def _tiles(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles (rows, contracted, columns) of a grouped product of those
    sizes: the largest of each list that divides its dimension (rows are
    multiples of 128). Sweep on the chip, `probe_moe.py parts`, [40960,
    2304] x [16, 2304, 1792], ms plain / against the weights transposed /
    the weights' cotangent: 512 x 1152 x 896 1.98 / 2.86 / 2.00, 512 x 768 x
    896 2.08 / 3.04 / 2.10, 1024 x 768 x 896 2.25 / 3.35 / 2.30, 256 x 1152
    x 896 2.59 / 3.54 / 2.18; 2304 deep or 1792 wide passes the VMEM. (The
    transposed product contracts 1,792 and takes 896 x 1152: 2.01.)"""
    pick = lambda x, sizes: next((t for t in sizes if x % t == 0), 128)
    return (pick(m, (512, 256)), pick(k, (1152, 1024, 896, 768, 512, 256)),
            pick(n, (1152, 1024, 896, 768, 512, 256)))


def grouped_products(kernels: bool, dtype):
    """-> (rows [W, m] x weights [Eh, m, n] -> [W, n]; rows [W, n] x the
    same weights, transposed -> [W, m]; rows [W, m], [W, n] -> the weights'
    cotangent [Eh, m, n]), each over `sizes` rows an expert: a product and
    its two transposes, by the Pallas kernels or by `lax.ragged_dot`. Rows
    past the groups' end come back undefined from the first two."""
    if not kernels:
        dims = jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=(0,), rhs_group_dimensions=())
        return (jax.lax.ragged_dot,
                lambda g, w, sizes: jax.lax.ragged_dot(
                    g, jnp.swapaxes(w, 1, 2), sizes),
                lambda a, g, sizes: jax.lax.ragged_dot_general(
                    a, g, sizes, dims))
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    kw = dict(preferred_element_type=dtype, tiling=_tiles,
              interpret=dispatch.interpret())
    return (lambda a, w, sizes: gmm(a, w, sizes, **kw),
            lambda g, w, sizes: gmm(g, w, sizes, transpose_rhs=True, **kw),
            lambda a, g, sizes: tgmm(a.T, g, sizes, **kw))


def held_window_rows(tokens: int, experts_per_token: int, num_experts: int,
                     held_count: int) -> int:
    """Rows of the first window: `HELD_WINDOW_FACTOR` times the even share
    of the held experts, no fewer than `HELD_WINDOW_MIN_TOKENS` a token,
    rounded up to a multiple of 128, at most every assignment there is (which
    is what `held` = all gets: one trip, always)."""
    total = tokens * experts_per_token
    if held_count >= num_experts:
        return total
    rows = max(int(HELD_WINDOW_FACTOR * total * held_count / num_experts),
               int(HELD_WINDOW_MIN_TOKENS * tokens), 1)
    return min(total, -(-rows // 128) * 128)


def further_window_rows(rows: int) -> int:
    """Rows of every window past a first one of `rows`:
    `FURTHER_WINDOW_SHARE` of it, rounded up to a multiple of 128."""
    return min(rows, -(-int(FURTHER_WINDOW_SHARE * rows) // 128) * 128)


def block_rows(rows: int, blocks: int = 20) -> int:
    """Rows of a block of the row passes over a window of `rows`: a whole
    number of the grouped products' row tiles (`_tiles`; of rows where the
    window is not made of tiles) that divides the window, one of `blocks`
    or the nearest below: 4,096 of 81,920, 1,536 of 30,720, 512 of 8,192.
    Sweep on the chip (`benchmarks/probe_moe.py rows`, PR 43; one layer
    alone, forward + backward, ms at a held total of the even share / of 2.5
    of it; 5, 10, 20, 40 blocks a window): mellum2's [16384, 2304], 16 of 64
    experts of 896, 34.60 / 59.03, 33.82 / 57.12, 33.40 / 56.41, 33.49 /
    56.65 (the whole window in one pass, PR 42: 51.24 / 61.29); kanana's
    [16384, 2048], 16 of 128 of 768, 16.17 / 23.14, 15.78 / 22.84, 15.43 /
    22.26, 15.66 / 22.79 (21.04 / 24.16); the hybrid's [8192, 2304], 8 of
    256 of 1024, a tenth 9.14 / 10.68, a twentieth 9.01 / 10.59 (10.16 /
    10.90)."""
    tile = _tiles(rows, 128, 128)[0] if rows % 128 == 0 else 1
    tiles = max(1, round(rows / tile / blocks))
    while rows // tile % tiles:
        tiles -= 1
    return tiles * tile


def _trips(held, rows: int, further_rows: int, further: int):
    """Windows that `held` sorted assignments reach into: the first, of
    `rows` rows, and as many of the `further` ones of `further_rows`."""
    return 1 + jnp.clip((held - rows + further_rows - 1) // further_rows,
                        0, further)


# A first window's two sums back to the tokens (the combine, and dx in the
# backward) are `block_sums`' grouped product over token-ordered rows where
# the grouped products are kernels and the tokens are whole blocks of this
# many, else `scatter_rows`' scatter-add (the CPU, a mesh, float32, odd
# widths; the further windows always: a Pallas call more costs set-up, as
# their `ragged_dot`). Probe on the chip (`benchmarks/probe_moe.py combine`,
# PR 50), ms a call at each routed cell's tokens / d / window rows / held
# rows. Columns: the scatter-add as dx calls it | with the combine's scaling
# pass before it | the token order, a sort | the same from a prefix sum and
# two integer scatters | the scaling pass over cut blocks | through the order
# | a plain pass through the order | one-hot + product at blocks of 128 /
# 256 / 512 tokens and `_tiles` | at 256 and `_sum_tiles` | the combine whole
# (order + scaling pass through it + product) at 128 / 256 / 512 | dx whole:
#   mellum2 16384/2304/81920/37,000  4.95 | 5.54 | 0.38 | 1.38 | 0.62 | 1.85
#     | 1.59 | 0.79 / 0.78 / 0.89 | 0.72 | 3.37 / 3.32 / 3.42 | 3.06 / 3.01 / 3.12
#   mellum2 .../60,000               7.97 | 8.85 | 0.36 | 1.38 | 0.91 | 2.75
#     | 2.37 | 1.02 / 0.96 / 1.20 | 0.88 | 4.53 / 4.41 / 4.65 | 4.08 / 3.97 / 4.20
#   kanana 16384/2048/30720/12,000   1.87 | 2.04 | 0.39 | 0.54 | 0.22 | 0.45
#     | 0.37 | 0.47 / 0.44 / 0.45 | 0.41 | 1.15 / 1.11 / 1.12 | 1.07 / 1.02 / 1.03
#   qwen3_next 16384/2048/25600/10,000  1.71 | 1.84 | 0.40 | 0.45 | 0.21 | 0.46
#     | 0.39 | 0.45 / 0.42 / 0.42 | 0.39 | 1.00 / 0.98 / 1.03 | 0.93 / 0.91 / 0.96
#   laguna 8192/3072/8192/3,000      1.03 | 1.03 | 0.38 | 0.21 | 0.23 | 0.21
#     | 0.22 | 0.31 / 0.28 / 0.28 | 0.27 | 0.48 / 0.46 / 0.47 | 0.46 / 0.44 / 0.45
#   the hybrid 8192/2304/8192/4,500  1.43 | 1.45 | 0.38 | 0.20 | 0.20 | 0.22
#     | 0.20 | 0.26 / 0.24 / 0.24 | 0.23 | 0.45 / 0.44 / 0.46 | 0.42 / 0.41 / 0.42
# (both ways' sums agree to the last bit at every shape but one, 2e-9 apart).
# A pass through the order is a row gather (0.9 ms for 37 k rows of 2,304)
# and the block's `dynamic_update_slice` (0.7), two and a half times the cut
# pass: the larger part of what the product's way costs. The order is made
# once a layer and kept for the backward (`RESIDUAL_NAMES`), so a layer pays
# 0.38 + 2.94 + 2.63 ms at mellum2's shape where the scatter-adds took 10.49.
TOKEN_BLOCK = 256


def scatter_rows(tok, vals, held, tokens: int):
    """Zeros [tokens, d] with `vals` [W, d] added at the tokens `tok`
    (`tokens`: skipped), as ONE scatter-add (float32 sums, rounded once),
    over the window's first half where its `held` filled rows end there: the
    rows past them are skipped either way, so the sums are the whole
    window's, but XLA sorts and permutes every row it is given (3.0 ms of a
    call's 7.8 at mellum2's shape), and a call costs 1.4-1.6 ms whatever its
    rows, 0.08 ms per 1,000 rows it adds and 0.01 per 1,000 it skips, so it
    is never cut into blocks (PERF.md section 6, PR 43). (A quarter as a
    third choice made the step's program large enough for XLA to recompute a
    product of 10 ms to fit it: same section.)"""
    half = -(-len(tok) // 2)
    add = lambda tok, vals: jnp.zeros(
        (tokens, vals.shape[1]), vals.dtype).at[tok].add(vals, mode="drop")
    return jax.lax.cond(
        held > half, add,
        lambda tok, vals: add(tok[:half], vals[:half]), tok, vals)


def token_order(tok, tokens: int, block: int):
    """The token of each of a window's rows (`tokens` where a row holds no
    held assignment) -> (the rows in a stable order by token, those that hold
    nothing last; their tokens in that order; how many held rows each block
    of `block` tokens has). Integers only. One sort that carries the rows
    along: on the chip it is 0.1 ms at 81,920 rows, where taking the tokens
    through the order afterwards (a gather of scalars) is 1.2 (the traced
    step, PERF.md section 6, PR 50)."""
    tok_t, by_token = jax.lax.sort(
        (tok, jnp.arange(len(tok), dtype=jnp.int32)), num_keys=1,
        is_stable=True)
    sizes = jnp.sum((tok // block)[:, None] == jnp.arange(tokens // block),
                    axis=0, dtype=jnp.int32)
    return by_token, tok_t, sizes


def _sum_tiles(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles (rows, tokens, columns) of `block_sums`' product: a block of
    tokens whole, the widest columns that divide the width."""
    return (_tiles(m, k, n)[0], k, next(
        (t for t in (2304, 2048, 1536, 1152, 1024, 768, 512, 256)
         if n % t == 0), 128))


def block_sums(tok_t, rows_t, sizes, tokens: int, block: int, tiling=None):
    """Zeros [tokens, d] with the rows `rows_t` [W, d] added at the tokens
    `tok_t`, both in `token_order`: the rows of a block of `block` tokens
    are one contiguous run, so the block's sums are a product one-hot^T
    [block, run] x rows [run, d], a grouped product whose groups are the
    token blocks (`megablox.tgmm`): float32 sums rounded once to the rows'
    dtype, zeros where a block has no row, no row past the groups' end
    read into a sum."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    onehot = ((tok_t % block)[:, None] == jnp.arange(block)).astype(
        rows_t.dtype)                                          # [W, block]
    return tgmm(onehot.T, rows_t, sizes,
                preferred_element_type=rows_t.dtype,
                tiling=tiling or _sum_tiles,
                interpret=dispatch.interpret()).reshape(
                    tokens, rows_t.shape[1])


def moe_ffn_held(
    x: jax.Array,          # [B, S, d] (cfg.dtype)
    router_w: jax.Array,   # [d, E]      E = every expert of the layer
    w_gate_up: jax.Array,  # [Eh, d, 2, F]  the experts held here
    w_down: jax.Array,     # [Eh, F, d]
    *,
    route: Callable,       # (x [T, d], router_w) -> (ids [T, k], weights)
    held_first: int = 0,
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """-> (the held experts' part of the layer's output [B, S, d], counters).

    Experts `held_first .. held_first + Eh` are the ones whose weights are
    given. What the other experts would add is left out. `route` is
    `sigmoid_route` or `softmax_route` with its keywords bound.

    The assignments, sorted by expert with the held ones first, are worked
    through in windows: the first, of `held_window_rows` rows (a margin over
    the held experts' even share, a row a token at least; every assignment
    where all are held), always, then a loop of as many smaller ones
    (`further_window_rows`) as the held assignments reach into. Each is a
    gather of the window's token rows, two grouped products over the rows the
    routing put there and a sum back to the tokens (a third grouped product,
    over the rows in token order, where the first window's are kernels, else
    a scatter-add: `TOKEN_BLOCK`); the products' grids stop at the
    groups' end and every other pass runs over row blocks (`block_rows`), as
    many as the window's held rows reach into, so the device work follows
    the rows the routing filled, a block at a time, and the window's margin
    costs memory: a routing within it takes the one window and a routing
    however skewed loses nothing.

    Counters (device scalars, float32): `assigned` (assignments that fell
    on held experts), `load_max` / `load_mean` (of a held expert, in
    assignments), `past_buffer` (assignments beyond the first window),
    `dropped` (assigned less the rows the windows counted as worked),
    `trips` (windows worked), `window_rows` (rows of the first window),
    `rows_worked` (rows of the blocks the row passes touched, over every
    window worked: whole blocks, the held rows at least)."""
    from ray_tpu.util import tracing

    B, S, d = x.shape
    T = B * S
    E, Eh, F = router_w.shape[-1], w_gate_up.shape[0], w_down.shape[1]
    xf = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        idx, wts = route(xf, router_w)
        k = idx.shape[-1]
        W = held_window_rows(T, k, E, Eh)
        W2 = further_window_rows(W)
        n_further = -(-(T * k - W) // W2)  # windows past the first, at most
        local = idx.reshape(T * k) - held_first
        local = jnp.where((local >= 0) & (local < Eh), local, Eh)
        counts = jnp.sum(local[:, None] == jnp.arange(Eh)[None, :], axis=0,
                         dtype=jnp.int32)                      # [Eh]
        ends = jnp.cumsum(counts)         # of each expert's run in `order`
        held = ends[-1]
        # Sorted by expert, held ones first; padded to whole windows.
        order = jnp.pad(jnp.argsort(local, stable=True),
                        (0, W + n_further * W2 - T * k))
        wflat = wts.reshape(T * k)
        # The windows and their transposes go by ONE routing: the sorted
        # list, the runs' ends and the weights carry the token order's name,
        # so that a remat policy keeps them beside the products' outputs
        # (`RESIDUAL_NAMES`). Formed again in a layer's recomputation they
        # can differ from the forward's by a token at a tie (XLA fuses the
        # recomputed layer its own way, and a rounding apart in x is another
        # expert), and every row behind that token then meets another row's
        # kept output: the layer's expert and router gradients came out
        # uncorrelated with the reference's on the chip (relative error 1.41
        # and 1.2, 3 seeds in 40; PERF.md section 6, PR 59).
        order, ends, wflat = (checkpoint_name(a, RESIDUAL_NAMES[2])
                              for a in (order, ends, wflat))
        trips = _trips(ends[-1], W, W2, n_further)
    w1 = w_gate_up.reshape(Eh, d, 2 * F).astype(dtype)
    w2 = w_down.astype(dtype)
    f32 = lambda a: a.astype(jnp.float32)

    # A grouped product leaves rows in no group undefined (zeros on the CPU,
    # whatever the buffer held on the TPU: NaN seen, chip run of PR 27),
    # forward and in every transposed product of the backward pass, and reads
    # none of them. Rows that hold no held assignment take the token index
    # T, one past the tokens: gathered from there they are zeros, scattered
    # to there they are skipped. So a window's passes follow the rows the
    # routing filled, each in the way that costs least on the chip
    # (`probe_moe.py rows` / `combine`, PERF.md section 6, PRs 43 and 50):
    # the two sums back to the tokens are one call over the window each
    # (`add_rows`: a grouped product over token-ordered rows or a
    # scatter-add; float32 sums of the whole window, rounded once), the
    # weights' cotangent one small scatter-add, and every other pass over a
    # [W, .] buffer (gather, mask, SiLU, scale, row sums, the rows into token
    # order, and their transposes) runs over row blocks (`block_rows`), as
    # many as the window's held rows reach into: a loop of dynamic length, a
    # block's rows cut out of the buffer and put back. Rows past the last
    # worked block hold whatever the buffer held (`lax.empty`, or the
    # product's output that the pass overwrites in place): no pass reads
    # them, the scatter-adds skip them and the sums' product masks them as
    # every grouped product does. Inside the last worked block,
    # what a product wrote past the held rows is zeroed once, in the pass
    # that applies the SiLU (and its transpose), since a NaN times a zero
    # weight is a NaN.
    def rows_of(wflat, order, ends, lo, n):
        """The window of rows lo .. lo + n of the sorted list -> the token
        of each row (T where the row holds no held assignment), its
        assignment (T k there), its weight (0 there), the experts' group
        sizes inside the window, which rows hold an assignment, how many."""
        rows = jax.lax.dynamic_slice(order, (lo,), (n,))
        ends_w = jnp.clip(ends, lo, lo + n) - lo
        valid = jnp.arange(n) < ends_w[-1]
        return (jnp.where(valid, rows // k, T), jnp.where(valid, rows, T * k),
                jnp.where(valid, wflat[rows], 0.0),
                jnp.diff(ends_w, prepend=0), valid[:, None], ends_w[-1])

    def row_passes(rows, held):
        """A window's row passes, for `held` of its `rows` filled:
        `each(body, init)` runs `body(at, carry)` for the first row `at` of
        every block the held rows reach into, `cut(a, at)` is that block of
        `a`, `put(buf, a, at)` writes it back; last, the rows of those
        blocks."""
        block = block_rows(rows)
        blocks = (held + block - 1) // block
        each = lambda body, init: jax.lax.fori_loop(
            0, blocks, lambda b, carry: body(b * block, carry), init)
        cut = lambda a, at: jax.lax.dynamic_slice_in_dim(a, at, block)
        put = lambda buf, a, at: jax.lax.dynamic_update_slice_in_dim(
            buf, a, at, 0)
        return each, cut, put, blocks * block

    take = lambda a, at: a.at[at].get(mode="fill", fill_value=0)

    def fresh(shape, dt, held):
        """An uninitialised buffer (`lax.empty`: an AllocateBuffer on the
        TPU, no fill), made inside a conditional on `held` (a count: never
        negative) because XLA hoists a bare one out of the scan over layers
        as loop-invariant and copies it back in every layer (0.92 + 0.36 ms
        a layer at mellum2's shape; +0.9% of the cell's rate without)."""
        make = lambda: jax.lax.empty(shape, dt)
        return jax.lax.cond(held >= 0, make, make)

    def add_rows(tok, held, cut, order_t):
        """How a window's rows [W, d] are summed back to the tokens, from
        the rows' tokens `tok` -> (`rows_at(a, at)`: the block at `at` of
        `a` [W, ...] in the order the sum takes its rows, `add(rows)`: the
        sums [T, d]). `order_t` is a first window's `token_order` (the rows
        in token order, summed by `block_sums`), () (a first window's rows
        as they lie, `scatter_rows`) or None, a further window's
        scatter-add; a first window's sum is counted where it is traced
        (`train.moe_combine.product` / `.scatter`)."""
        if order_t is not None:
            tracing.observe("train.moe_combine." + (
                "product" if order_t else "scatter"), 0, slow=False)
        if not order_t:
            return cut, lambda rows: scatter_rows(tok, rows, held, T)
        by, tok_t, sizes_t = order_t
        return (lambda a, at: a.at[cut(by, at)].get(
            mode="promise_in_bounds"),
                lambda rows: block_sums(tok_t, rows, sizes_t, T,
                                        TOKEN_BLOCK))

    # The first window's products by the kernels where `use_kernels` says so;
    # the further ones, which an even routing never reaches, by `ragged_dot`
    # always: every Pallas call in a program is lowered in Python when the
    # program is traced (0.4 s each on the chip's worker), and the loop's
    # eight would put `setup_s` past its bound (PERF.md section 6, PR 34).
    s = dispatch.site()
    kernels = use_kernels(s.platform, dtype, (d, F), s.on_mesh)
    first = lambda order_t: (0, W, grouped_products(kernels, dtype), order_t)
    further = lambda i: (W + (i - 1) * W2, W2,
                         grouped_products(False, dtype), None)

    def swiglu(gu, valid):
        gu = jnp.where(valid, gu, 0)  # on the way in: transposed, it zeroes
        return jax.nn.silu(gu[:, :F]) * gu[:, F:]  # the cotangent going out

    def window(xf, w1, w2, wflat, order, ends, lo, rows, products, order_t):
        """-> (the window's part of the output [T, d], how many of its rows
        were held assignments, the rows its passes worked, the two products'
        outputs)."""
        grouped = products[0]
        tok, _, wrow, sizes, valid, n = rows_of(wflat, order, ends, lo, rows)
        each, cut, put, touched = row_passes(rows, n)
        rows_at, add = add_rows(tok, n, cut, order_t)
        xb = each(lambda at, xb: put(
            xb, take(xf, cut(tok, at)).astype(dtype), at),
            fresh((rows, d), dtype, n))
        gu = grouped(xb, w1, sizes)                            # [W, 2F]
        act = each(lambda at, act: put(
            act, swiglu(cut(gu, at), cut(valid, at)), at),
            fresh((rows, F), dtype, n))
        yb = grouped(act, w2, sizes)                           # [W, d]
        # (Written over the gathered rows, which the first product has read.)
        scaled = each(lambda at, xb: put(
            xb, (f32(rows_at(yb, at)) * rows_at(wrow, at)[:, None]).astype(
                dtype), at), xb)
        return add(scaled), n, touched, (gu, yb)

    def window_t(xf, w1, w2, wflat, order, ends, lo, rows, products, order_t,
                 gu, yb, dy):
        """The window's transpose: the output's cotangent dy [T, d] -> those
        of xf, w1, w2, wflat, given the products' outputs. A pass writes
        over the buffer it has read where the shapes allow: `yb`'s blocks
        become its cotangent's, `gu`'s its cotangent's, and the SiLU's
        cotangent's the SiLU's output."""
        _, grouped_t, grouped_tw = products
        tok, at_w, wrow, sizes, valid, n = rows_of(wflat, order, ends, lo,
                                                   rows)
        each, cut, put, _ = row_passes(rows, n)
        rows_at, add = add_rows(tok, n, cut, order_t)

        def gathers(at, carry):
            xb, yb, dwrow = carry
            t = cut(tok, at)
            dyb = f32(take(dy, t))                             # [block, d]
            return (put(xb, take(xf, t).astype(dtype), at),
                    put(yb, (dyb * cut(wrow, at)[:, None]).astype(dtype), at),
                    put(dwrow, jnp.sum(f32(cut(yb, at)) * dyb, axis=-1), at))

        xb, dyb, dwrow = each(gathers, (
            fresh((rows, d), dtype, n), yb,
            fresh((rows,), jnp.float32, n)))

        def swiglu_t(at, carry):
            dact, gu = carry
            act, t = jax.vjp(lambda gu: swiglu(gu, cut(valid, at)),
                             cut(gu, at))
            return put(dact, act, at), put(gu, t(cut(dact, at))[0], at)

        act, dgu = each(swiglu_t, (grouped_t(dyb, w2, sizes), gu))
        dxb = grouped_t(dgu, w1, sizes).astype(xf.dtype)       # [W, d]
        if order_t:  # a pass more: the rows into the sum's order
            dxb = each(lambda at, rows_t: put(rows_t, rows_at(dxb, at), at),
                       fresh((rows, d), xf.dtype, n))
        return (add(dxb),
                grouped_tw(xb, dgu, sizes), grouped_tw(act, dyb, sizes),
                jnp.zeros_like(wflat).at[at_w].add(dwrow, mode="drop"))

    # A loop of dynamic length has no reverse rule, and a scan of
    # `lax.cond`s differentiated as it stands hands the scan its
    # loop-invariant operands (x, both weight stacks) as residuals of every
    # iteration (+2 GB at 8 k tokens): so the windows have a backward rule of
    # their own, the same trips over each window's transpose, summed as the
    # forward sums, in the operands' own types. The first window, which is
    # always worked and as a rule the only one, stands outside the loop: its
    # products' outputs are residuals a remat policy can keep
    # (`RESIDUAL_NAMES`) and its gradients start the sums, where a loop's
    # would be added to zeros.
    def worked(*args):
        at, trips = args[:6], args[6]
        *out, res = window(*at, *first(args[7:]))

        def body(i, carry):
            return tuple(a + b for a, b in zip(
                carry, window(*at, *further(i))[:3]))

        return jax.lax.fori_loop(1, trips, body, tuple(out)), res

    @jax.custom_vjp
    def worked_windows(*args):  # xf, w1, w2, wflat, order, ends, trips and
        return worked(*args)[0]  # the first window's token order, if made

    def fwd(*args):
        out, res = worked(*args)
        return out, (args, tuple(map(checkpoint_name, res, RESIDUAL_NAMES)))

    def bwd(res, ct):
        args, first_res = res
        at, trips = args[:6], args[6]

        def body(i, acc):
            win = at + further(i)
            g = window_t(*win, *window(*win)[3], ct[0])
            return tuple(a + b for a, b in zip(acc, g))

        acc = jax.lax.fori_loop(1, trips, body,
                                window_t(*at, *first(args[7:]), *first_res,
                                         ct[0]))
        return acc + tuple(np.zeros(a.shape, jax.dtypes.float0)
                           for a in args[4:])

    worked_windows.defvjp(fwd, bwd)

    with jax.named_scope("moe.experts"):
        order_t = ()
        if kernels and T % TOKEN_BLOCK == 0:
            # Once a layer, kept for the backward as the products' outputs.
            by, tok_t, sizes_t = token_order(
                rows_of(wflat, order, ends, 0, W)[0], T, TOKEN_BLOCK)
            order_t = (checkpoint_name(by, RESIDUAL_NAMES[2]),
                       checkpoint_name(tok_t, RESIDUAL_NAMES[2]), sizes_t)
        y, n_worked, touched = worked_windows(xf, w1, w2, wflat, order, ends,
                                              trips, *order_t)
    counters = {
        "assigned": f32(held),
        "load_max": f32(jnp.max(counts)),
        "load_mean": f32(held) / Eh,
        "past_buffer": f32(jnp.maximum(held - W, 0)),
        "dropped": f32(held - n_worked),
        "trips": jnp.float32(trips),
        "window_rows": jnp.float32(W),
        "rows_worked": f32(touched),
    }
    return y.reshape(B, S, d), counters
