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
  products (`lax.ragged_dot`, no one-hot) a window of rows at a time, and
  returns its own experts' part. With every expert held and the expert dim
  sharded over an `expert` mesh axis this is expert parallelism.
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
    dispatch = disp.sum(1)                                   # [T, E, C]

    # Route tokens to expert buffers: [E, C, d].
    expert_in = jnp.einsum(
        "tec,td->ecd", dispatch.astype(dtype), xf.astype(dtype))
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


# A window of gathered assignments is this many times the held experts'
# even share of them (tokens x k x held / experts). An even routing fits one
# window with room for a few times the mean load on one expert; a skewed one
# takes further trips of the same loop.
HELD_WINDOW_FACTOR = 4.0


def held_window_rows(tokens: int, experts_per_token: int, num_experts: int,
                     held_count: int) -> int:
    """Rows of one window: `HELD_WINDOW_FACTOR` times the even share of the
    held experts, a multiple of 128, at most every assignment there is
    (which is what `held` = all gets)."""
    total = tokens * experts_per_token
    if held_count >= num_experts:
        return total
    rows = int(HELD_WINDOW_FACTOR * total * held_count / num_experts)
    return min(total, -(-max(rows, 1) // 128) * 128)


def _trips(held, rows: int, windows: int):
    """Windows of `rows` rows that `held` sorted assignments reach into."""
    return jnp.clip((held + rows - 1) // rows, 1, windows)


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
    through in windows of `held_window_rows` rows by one loop of as many
    trips as the held assignments reach into (one, as a rule; up to all
    tokens x k rows), each a grouped product over the rows the routing put
    there: a routing however skewed loses nothing and an even one pays for
    its own rows in one window.

    Counters (device scalars, float32): `assigned` (assignments that fell
    on held experts), `load_max` / `load_mean` (of a held expert, in
    assignments), `past_buffer` (assignments beyond the first window),
    `dropped` (assigned less the rows the loop's trips counted as worked)."""
    B, S, d = x.shape
    T = B * S
    E, Eh, F = router_w.shape[-1], w_gate_up.shape[0], w_down.shape[1]
    xf = x.reshape(T, d)
    with jax.named_scope("moe.route"):
        idx, wts = route(xf, router_w)
        k = idx.shape[-1]
        W = held_window_rows(T, k, E, Eh)
        windows = -(-T * k // W)
        local = idx.reshape(T * k) - held_first
        local = jnp.where((local >= 0) & (local < Eh), local, Eh)
        counts = jnp.sum(local[:, None] == jnp.arange(Eh)[None, :], axis=0,
                         dtype=jnp.int32)                      # [Eh]
        ends = jnp.cumsum(counts)         # of each expert's run in `order`
        held = ends[-1]
        # Sorted by expert, held ones first; padded to whole windows.
        order = jnp.pad(jnp.argsort(local, stable=True),
                        (0, windows * W - T * k))
        wflat = wts.reshape(T * k)
        trips = _trips(held, W, windows)
    w1 = w_gate_up.reshape(Eh, d, 2 * F).astype(dtype)
    w2 = w_down.astype(dtype)

    def window(xf, w1, w2, wflat, order, ends, i):
        """Rows i W .. (i + 1) W of the sorted list -> (their part of the
        output [T, d], how many of them were held assignments)."""
        lo = i * W
        rows = jax.lax.dynamic_slice(order, (lo,), (W,))
        ends_w = jnp.clip(ends, lo, lo + W) - lo
        sizes = jnp.diff(ends_w, prepend=0)
        # Rows in no group are left undefined by a grouped product (zeros on
        # the CPU, whatever the buffer held on the TPU: NaN seen, chip run of
        # PR 27), forward and in every transposed product of the backward
        # pass. The masks zero them on the way out and, transposed, on the
        # way back.
        valid = (jnp.arange(W) < ends_w[-1])[:, None]
        tok = rows // k
        xb = jnp.where(valid, xf[tok], 0).astype(dtype)        # [W, d]
        gu = jnp.where(valid, jax.lax.ragged_dot(xb, w1, sizes), 0)
        act = jax.nn.silu(gu[:, :F]) * gu[:, F:]
        yb = jnp.where(valid, jax.lax.ragged_dot(act, w2, sizes), 0)
        yb = yb * jnp.where(valid[:, 0], wflat[rows], 0.0)[:, None].astype(
            yb.dtype)
        return jnp.zeros((T, d), yb.dtype).at[tok].add(yb), ends_w[-1]

    # A loop of dynamic length has no reverse rule, and a scan of
    # `lax.cond`s differentiated as it stands hands the scan its
    # loop-invariant operands (x, both weight stacks) as residuals of every
    # iteration (+2 GB at 8 k tokens): so the loop has a backward rule of its
    # own, the same trips over each window's transpose, summed as the
    # forward sums, in the operands' own types.
    @jax.custom_vjp
    def worked_windows(xf, w1, w2, wflat, order, ends, trips):
        def body(i, carry):
            y, n = window(xf, w1, w2, wflat, order, ends, i)
            return carry[0] + y, carry[1] + n

        return jax.lax.fori_loop(
            0, trips, body, (jnp.zeros((T, d), dtype), jnp.int32(0)))

    def fwd(*args):
        return worked_windows(*args), args

    def bwd(res, ct):
        diff, (order, ends, trips) = res[:4], res[4:]

        def body(i, acc):
            _, vjp = jax.vjp(lambda *a: window(*a, order, ends, i)[0], *diff)
            return tuple(a + g for a, g in zip(acc, vjp(ct[0])))

        acc = jax.lax.fori_loop(0, trips, body,
                                tuple(jnp.zeros_like(a) for a in diff))
        return acc + tuple(np.zeros(a.shape, jax.dtypes.float0)
                           for a in res[4:])

    worked_windows.defvjp(fwd, bwd)

    with jax.named_scope("moe.experts"):
        y, worked = worked_windows(xf, w1, w2, wflat, order, ends, trips)
    f32 = lambda a: a.astype(jnp.float32)
    counters = {
        "assigned": f32(held),
        "load_max": f32(jnp.max(counts)),
        "load_mean": f32(held) / Eh,
        "past_buffer": f32(jnp.maximum(held - W, 0)),
        "dropped": f32(held - worked),
    }
    return y.reshape(B, S, d), counters
