"""Tensor parallelism with the residual stream sharded along the sequence
(Megatron's sequence parallelism), and each projection's collective run in
steps behind its own matmul (a collective matmul).

A projection whose weight is cut over `tensor` is closed by a sum over
`tensor`. With the residual replicated over that axis the sum is one
all-reduce that the very next op waits for. With the residual's rows cut
over `tensor` (the logical axis `seq_res`) the same bytes move as an
all-gather into the column-cut products and a reduce-scatter out of the
row-cut ones, and both can be cut along the ROWS into `extent` steps, step
i's product running while step i + 1's block is on the wire:

- `gather_matmul`: multiply the rows you hold while the neighbour's arrive;
- `matmul_scatter`: multiply the rows that are another rank's first and send
  the partial sums round the ring while multiplying your own;
- `gathered_mlp`: both around a dense feed-forward in one body, where the
  order of the rows between the two does not matter and no block is placed
  or picked by a rank-dependent index.

Nothing is cut along a contraction, so every output element is the sum of
the same `extent` partial products it is under the all-reduce. The bodies
are `jax.shard_map`s, manual over every axis: a weight arrives as it is
stored and is gathered by hand over the axes that cut it beside the ring's
(`fsdp`), so that its gradient, two blocks' products added, is summed over
them once (`_wgrad`). The two rings are
`custom_vjp`s, each the other's backward (a gather's is a scatter of dx and
a second gather for dw; a scatter's is one gather for both): plain autodiff
gives the same sums, but XLA fuses the add of what the ring brought into the
product that should run while it travels, and the halves are exposed again
(PERF.md section 6, PR 37). `_tie` and `_after` keep a step's product and
the block on the wire apart. Their outputs carry `RESIDUAL_NAMES`, which the
"dots" remat policy keeps as it keeps a dot's (models/transformer.py).

`plan()` decides from what it can observe, with no option: the sharding
context's mesh and rules, and the sequence's length.
"""
from __future__ import annotations

import functools
import math
import types
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel import sharding as shd
from ray_tpu.util import tracing

# The residual's logical axes under a plan, and everywhere else.
RESIDUAL = ("batch", "seq_res", "embed")
ACTIVATION = ("batch", "seq_act", "embed")


# What a gathered product (whole, or the dense MLP's blocks) and a scattered
# sum are named (`checkpoint_name`): the outputs a `dot_general` would have
# under `dots_with_no_batch_dims_saveable`.
RESIDUAL_NAMES = ("tp.gathered", "tp.scattered")


def _letters(eq: str) -> Tuple[str, str, str]:
    ins, out = eq.split("->")
    x, w = ins.split(",")
    return x, w, out


def _tie(work, wire):
    """`work` (a step's partial sum) and `wire` (what travelled meanwhile)
    through one optimization barrier: the add of the two cannot be fused
    into the product that makes `work`, which would then wait for the
    transfer it is there to hide."""
    return lax.optimization_barrier((work, wire))


def _after(work, wire):
    """`wire` (the block that travelled meanwhile), usable once `work` (a
    step's products) is under way: one element of every product goes
    through the barrier with it, not the products themselves, so what
    follows them (a save into the scan's stack) can still be fused into
    them. XLA may make that element by a product of its own, so this orders
    less strictly than `_tie`: enough where nothing downstream of `wire` is
    added to `work`."""
    marks = [a.reshape(-1)[:1] for a in jax.tree.leaves(work)]
    return lax.optimization_barrier((marks, wire))[1]


def _wgrad(lx: str, ly: str, lw: str, xs, dys, w: jax.Array) -> jax.Array:
    """`w`'s gradient from the blocks of its product's two sides: the
    blocks' products added on the device (one product over the blocks laid
    end to end would copy every operand first), then summed over the axes
    the batch is cut over and `w` is not (`data`). Over an axis that cuts
    both (`fsdp`) the sum is the transpose of `Overlap._gathered`'s gather:
    once a weight either way."""
    dw = sum(jnp.einsum(f"{lx},{ly}->{lw}", x, dy)
             for x, dy in zip(xs, dys)).astype(w.dtype)
    over = tuple(jax.typeof(dw).vma - jax.typeof(w).vma)
    return lax.psum(dw, over) if over else dw


@functools.lru_cache(maxsize=None)
def _ring(axis: str, n: int):
    """A ring of `n` ranks over `axis`, to be used inside a `shard_map`
    over it. Blocks are listed in the order they arrive: block k is the
    rows of rank `index - k`.

    gather(eqs, x, ws) -> [[einsum(eq, block k of x, w) for eq, w] for k]
    scatter(eq, blocks, w) -> sum over ranks of einsum(eq, ., w) for the
    rows a rank holds, blocks[k] being ITS operand for the rows of rank
    `index - k`.
    place(blocks) -> the whole sequence in its own order; pick(whole) -> its
    blocks.
    whole(w, dim) -> `w`, cut over the axis along `dim`, gathered."""
    perm = [(i, (i + 1) % n) for i in range(n)]
    send = lambda a: lax.ppermute(a, axis, perm)

    def gather_blocks(x, use):
        """use(k, block k) for each block as it arrives, the next on the
        wire meanwhile."""
        out = []
        for k in range(n):
            nxt = send(x) if k + 1 < n else None
            out.append(use(k, x))
            if nxt is not None:
                nxt = _after(out[-1], nxt)
            x = nxt
        return out

    def scatter_sum(partial):
        """Step k makes partial((k + 1) % n), the product for the rows of
        rank `index - (k + 1)` (a rank's own last), while the sums so far
        travel, and adds the two."""
        acc = None
        for k in range(n):
            wire = send(acc) if acc is not None else None
            p = partial((k + 1) % n)
            if wire is not None:
                p, wire = _tie(p, wire)
                p = wire + p
            acc = p
        return acc

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def gather(eqs, x, ws):
        return gather_fwd(eqs, x, ws)[0]

    def gather_fwd(eqs, x, ws):
        ys = gather_blocks(x, lambda k, blk: [
            jnp.einsum(eq, blk, w) for eq, w in zip(eqs, ws)])
        return ys, (x, ws)

    def gather_bwd(eqs, res, dys):
        x, ws = res
        lets = [_letters(eq) for eq in eqs]
        # x's rows set out once the cotangents are here, behind dx's first
        # product: sent as soon as the layer's backward begins (x is known
        # from the start) they share the wire with the transfer the layer's
        # backward is waiting for.
        dys, x = _tie(dys, x)

        def dx(k):  # of the rows of rank `index - k`
            return sum(jnp.einsum(f"{ly},{lw}->{lx}", dy, w)
                       for dy, w, (lx, lw, ly) in zip(dys[k], ws, lets))

        # Two rings in one pass: x's blocks come round again for dw while
        # dx's partial sums travel.
        acc, blocks = None, []
        for k in range(n):
            nxt = send(x) if k + 1 < n else None
            wire = send(acc) if acc is not None else None
            p = dx((k + 1) % n)
            p, (nxt, wire) = _tie(p, (nxt, wire))
            acc = p if wire is None else wire + p
            blocks.append(x)
            x = nxt
        dws = [_wgrad(lx, ly, lw, blocks, [dy[j] for dy in dys], w)
               for j, (w, (lx, lw, ly)) in enumerate(zip(ws, lets))]
        return acc.astype(res[0].dtype), dws

    gather.defvjp(gather_fwd, gather_bwd)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def scatter(eq, blocks, w):
        return scatter_fwd(eq, blocks, w)[0]

    def scatter_fwd(eq, blocks, w):
        out = scatter_sum(lambda k: jnp.einsum(eq, blocks[k], w))
        return checkpoint_name(out, RESIDUAL_NAMES[1]), (blocks, w)

    def scatter_bwd(eq, res, dout):
        blocks, w = res
        la, lw, lo = _letters(eq)
        douts = []

        def use(k, blk):  # blk: dout of the rows of rank `index - k`
            douts.append(blk)
            return jnp.einsum(f"{lo},{lw}->{la}", blk, w).astype(
                blocks[k].dtype)

        das = gather_blocks(dout, use)
        return das, _wgrad(la, lo, lw, blocks, douts, w)

    scatter.defvjp(scatter_fwd, scatter_bwd)

    def _place(blocks):
        # A window of n blocks out of the blocks laid end to end twice over:
        # XLA reads it straight from the blocks (no branch a rank, no block
        # written twice).
        rows = blocks[0].shape[1]
        twice = jnp.concatenate(
            [blocks[(n - 1 - j) % n] for j in range(2 * n - 1)], 1)
        return lax.dynamic_slice_in_dim(
            twice, (n - 1 - lax.axis_index(axis)) * rows, n * rows, 1)

    def _pick(a):
        rows, r = a.shape[1] // n, lax.axis_index(axis)
        return [lax.dynamic_slice_in_dim(a, ((r - k) % n) * rows, rows, 1)
                for k in range(n)]

    # Each the other's transpose (a permutation of the blocks), said so:
    # autodiff's own would scatter into zeros and add.
    place, pick = jax.custom_vjp(_place), jax.custom_vjp(_pick)
    place.defvjp(lambda blocks: (_place(blocks), None),
                 lambda _, d: (_pick(d),))
    pick.defvjp(lambda a: (_pick(a), None), lambda _, ds: (_place(ds),))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def whole(w, dim):
        return lax.all_gather(w, axis, axis=dim, tiled=True)

    def whole_bwd(dim, _, dw):
        # The gather's transpose as the same ring: a rank's cut of dw goes
        # round collecting every rank's share, on permutes that XLA runs
        # behind what follows (its own reduce-scatter here holds the device:
        # PERF.md section 6, PR 37).
        size, r = dw.shape[dim] // n, lax.axis_index(axis)
        return (scatter_sum(lambda k: lax.dynamic_slice_in_dim(
            dw, ((r - k) % n) * size, size, dim)),)

    whole.defvjp(lambda w, dim: (whole(w, dim), None), whole_bwd)
    return types.SimpleNamespace(gather=gather, scatter=scatter, place=place,
                                 pick=pick, whole=whole)


class Overlap:
    """One traced body's decomposition: the axis the residual's rows are cut
    over, its extent (the steps of every decomposed product), the rows a
    rank holds, and the products that took the decomposed path (`took`)."""

    def __init__(self, mesh: Mesh, rules: shd.Rules, axis: str, rows: int,
                 batch=None):
        self.mesh, self.rules, self.axis = mesh, rules, axis
        self.extent, self.rows = mesh.shape[axis], rows
        self.batch = batch  # the batch's PartitionSpec entry (its mesh axes)
        self.took: List[str] = []

    def observe(self) -> None:
        """`train.tp_overlap`, one observation a traced layer body: a count
        in the phase table, the attributes on the annotation inside a
        profiler session. No count: the mechanism never engaged."""
        tracing.observe("train.tp_overlap", 0, slow=False, axis=self.axis,
                        extent=self.extent, steps=self.extent,
                        rows=self.rows, products=",".join(self.took))

    def _weight(self, logical: shd.LogicalSpec):
        """(a weight's PartitionSpec, [(axis, dim) for every cut but the
        ring's]), or None: no dim is cut over the ring's axis, or some dim
        over two axes at once (the caller keeps today's product)."""
        spec = shd.logical_to_mesh_spec(logical, self.rules, self.mesh)
        if self.axis not in spec or any(
                e is not None and not isinstance(e, str) for e in spec):
            return None
        return spec, [(e, j) for j, e in enumerate(spec)
                      if e not in (None, self.axis)]

    def _gathered(self, w: jax.Array, cuts) -> jax.Array:
        """A weight whole but for the ring's cut (`fsdp`'s gather of it, by
        hand: its transpose is the ONE sum a gradient takes over the batch's
        axes, where the partitioner would close each block's product with
        its own)."""
        for a, j in cuts:
            w = _ring(a, self.mesh.shape[a]).whole(w, j)
        return w

    def _rows(self, letters: str, lw: str = "", w_spec: P = P()) -> P:
        """The PartitionSpec of an activation (`letters`, batch first): cut
        over the ring's axis along its rows (no weight named), or along the
        letter that names a dim of a weight (`lw`, `w_spec`) cut over it."""
        if not lw:
            return P(self.batch, self.axis)
        return P(self.batch, *(
            self.axis if c in lw and w_spec[lw.index(c)] == self.axis
            else None for c in letters[1:]))

    def _shard_map(self, body: Callable, in_specs, out_specs):
        """`body` manual over every axis of the mesh: over the ring's and
        the batch's because it moves rows and sums gradients by hand, over
        the rest (where the operands are whole) because the CPU's compiler
        aborts on a bfloat16 sum inside a body that leaves an axis to the
        partitioner (`AllReducePromotion`, jaxlib 0.9.0)."""
        return jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs)

    def gather_matmul(
        self, name: str, x: jax.Array,
        products: Sequence[Tuple[str, jax.Array, shd.LogicalSpec]],
    ) -> Optional[List[jax.Array]]:
        """[einsum(eq, x, w) for (eq, w, w's logical axes)], `x` [B, S, d]
        with its rows (dim 1) cut over the axis and every `w` cut along an
        output dim: the rows go round the ring, each block multiplied as it
        arrives and placed at its rows. None if a weight is not cut over the
        axis (the caller keeps today's product)."""
        n, axis = self.extent, self.axis
        weights = [self._weight(logical) for _, _, logical in products]
        if any(w is None for w in weights):
            return None
        eqs = tuple(eq for eq, _, _ in products)

        def out_spec(eq, w_spec):  # cut where the weight's dim of a letter is
            _, lw, lo = _letters(eq)
            return self._rows(lo, lw, w_spec)

        def body(x, *ws):
            ring = _ring(axis, n)
            ws = [self._gathered(w, cuts)
                  for w, (_, cuts) in zip(ws, weights)]
            blocks = ring.gather(eqs, x, ws)
            return [checkpoint_name(ring.place([blk[j] for blk in blocks]),
                                    RESIDUAL_NAMES[0])
                    for j in range(len(ws))]

        outs = self._shard_map(
            body, (self._rows("bsd"),) + tuple(spec for spec, _ in weights),
            [out_spec(eq, spec) for eq, (spec, _) in zip(eqs, weights)],
        )(x, *[w for _, w, _ in products])
        self.took.append(name)
        return outs

    def matmul_scatter(self, name: str, a: jax.Array, eq: str, w: jax.Array,
                       logical: shd.LogicalSpec) -> Optional[jax.Array]:
        """einsum(eq, a, w), `w` and `a` cut along the contraction, the sum
        over the axis cut along the rows (dim 1 of `a` and of the result):
        the partial sums travel the ring, each rank adding its own product
        of the rows they belong to, the rank that keeps them last."""
        n, axis = self.extent, self.axis
        weight = self._weight(logical)
        if weight is None:
            return None
        w_spec, cuts = weight
        la, lw, lo = _letters(eq)

        def body(a, w):
            ring = _ring(axis, n)
            return ring.scatter(eq, ring.pick(a), self._gathered(w, cuts))

        out = self._shard_map(body, (self._rows(la, lw, w_spec), w_spec),
                              self._rows(lo))(a, w)
        self.took.append(name)
        return out

    def gathered_mlp(self, names: Tuple[str, str], h: jax.Array, eq_in: str,
                     w_in: jax.Array, in_logical: shd.LogicalSpec,
                     act: Callable[[jax.Array], jax.Array], w_down: jax.Array,
                     down_logical: shd.LogicalSpec) -> Optional[jax.Array]:
        """A dense feed-forward, act(einsum(eq_in, h, w_in)) @ w_down, rows
        cut over the axis going in and coming out, in one body: the first
        product's blocks stay in the order they arrived in, which is the
        order the second one sends them back in, so nothing is placed or
        picked by a rank's index."""
        n, axis = self.extent, self.axis
        w_in_, w_down_ = self._weight(in_logical), self._weight(down_logical)
        if w_in_ is None or w_down_ is None:
            return None

        def body(h, w_in, w_down):
            ring = _ring(axis, n)
            w_in = self._gathered(w_in, w_in_[1])
            blocks = [act(checkpoint_name(y, RESIDUAL_NAMES[0]))
                      for y, in ring.gather((eq_in,), h, [w_in])]
            return ring.scatter("bsf,fd->bsd", blocks,
                                self._gathered(w_down, w_down_[1]))

        rows = self._rows("bsd")
        out = self._shard_map(body, (rows, w_in_[0], w_down_[0]),
                              rows)(h, w_in, w_down)
        self.took.extend(names)
        return out


def plan(batch: int, seq_len: int) -> Optional[Overlap]:
    """The decomposition for activations `[batch, seq_len, d]` under the
    current sharding context, or None: no context, rules without `seq_res`,
    an axis of extent 1 (or absent), rows the extent does not divide (a
    decode tick), a batch its mesh axes do not divide (the partitioner pads
    such a batch; a `shard_map` takes none), or a mesh whose `seq` axis
    already cuts the activations' rows (context parallelism keeps today's
    layout)."""
    ctx = shd.current_sharding_ctx()
    if ctx is None:
        return None
    mesh, rules = ctx
    by, res, _ = shd.logical_to_mesh_spec(RESIDUAL, rules, mesh)
    act = shd.logical_to_mesh_spec(ACTIVATION, rules, mesh)[1]
    if not isinstance(res, str) or act is not None:
        return None
    axes = () if by is None else (by,) if isinstance(by, str) else by
    if seq_len % mesh.shape[res] or batch % math.prod(
            mesh.shape[a] for a in axes):
        return None
    return Overlap(mesh, rules, res, seq_len // mesh.shape[res], by)
