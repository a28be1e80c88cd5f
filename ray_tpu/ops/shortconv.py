"""The gated short convolution's core: y = Cg * conv_K(Bg * x).

A double-gated short convolution IS its layer's token mixer (LFM2): the
joint projection p = u W_in lies [B, S, 3C] as [Bg ; Cg ; x], and

    z_t = Bg_t * x_t
    c_t = sum_j w[j] z_{t-K+1+j}      depthwise, causal: w[K-1] meets z_t,
                                      z before a sequence's start is zero
    y_t = Cg_t * c_t

with NO activation and no bias. `ops/kda.mixer_conv` is another function
(SiLU inside its kernel, no gate operand): its helpers are shared, not it.

`gated_conv` is the entry point and dispatches on what it observes
(`use_kernels`, no knob), as ops/selective_scan.py does:

- **`gated_conv_pallas`**: one forward and one backward Pallas (Mosaic)
  kernel under a `jax.custom_vjp`, on a TPU with no multi-device mesh, for
  channels of whole lane tiles. A grid step is a batch row's block of rows
  over ALL the columns: the three parts come through three BlockSpecs on p
  by column offset, so p is read where it lies (no slice of the
  projection's output, none of W_in), and the backward writes d[Bg ; Cg ; x]
  as ONE [B, S, 3C] tensor, the layout the projection's backward reads. The
  z is widened once into a float32 scratch and worked `_CONV_CHUNK` rows by
  a lane slice at a time (`kda._each_piece`), a tap the piece's aligned
  window of rows rotated along the sublanes (`kda._conv_taps`). The
  arithmetic is float32 with ONE rounding to p's dtype at each store. The
  forward walks a sequence's blocks first to last and keeps z's last
  `_HALO` rows in that scratch for the block behind them (zeros at a
  sequence's first block): no row is read twice. The backward walks them
  last to first, recomputes z and c from p and w (the only residuals:
  nothing is named for a remat policy), carries the first `_HALO` rows of
  dc = dy * Cg in scratch for the rows before them (dz_t takes the next
  K - 1 rows' terms), reads the `_HALO` rows of Bg and x before a block
  through two further BlockSpecs on p (z there is not yet made: zeros at a
  sequence's first block) and sums dw in float32 scratch across batches
  and row blocks, written once as [8, C] (dw's K rows).
- **`gated_conv_xla`**: the three lines above in plain XLA: the path on the
  CPU (every tier-1 model test), for channels that are no whole tiles and
  under a mesh, and the kernels' reference in the tests.

By bytes a token at C = 2,048 in bfloat16 the forward moves 16 KB (12 read,
4 written) and the backward 28 KB (16 read, 12 written): 0.66 and 1.12 ms a
layer at 32,768 tokens over 819 GB/s (benchmarks/probe_shortconv.py times
both bodies there; PERF.md section 6, PR 60).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .kda import (_CONV_CHUNK, _CONV_COLS, _HALO, _conv_taps, _each_piece,
                  _fold8, _iota, _rows_at, _tree_sum, short_conv)

_F32 = jnp.float32
SCOPE = "shortconv.core"
ROWS = (512, 256)   # a block's rows, forward and backward
_VMEM_BYTES = 96 * 1024 * 1024


def _widen_product(bg_ref, x_ref, ze_ref, rows_left, work):
    """z = Bg * x of the block as float32 in `ze_ref`, behind its first
    `_HALO` rows (the caller's: z of the rows before the block); rows past
    the sequence's end (`rows_left`, None where the blocks are whole) read
    as zeros."""
    def piece(base, cs):
        rows = pl.ds(base, _CONV_CHUNK)
        z = bg_ref[0, rows, cs].astype(_F32) * x_ref[0, rows, cs].astype(_F32)
        if rows_left is not None:
            z = jnp.where(_iota(z.shape, 0) + base < rows_left, z, 0.0)
        ze_ref[pl.ds(_HALO + base, _CONV_CHUNK), cs] = z

    _each_piece(*bg_ref.shape[1:], piece, work=work)


def _fwd_kernel(bg_ref, cg_ref, x_ref, w_ref, y_ref, ze_ref, *, K, work):
    bs, C = bg_ref.shape[1:]

    @pl.when(pl.program_id(1) == 0)
    def _():  # nothing precedes a sequence's first block
        ze_ref[:_HALO] = jnp.zeros((_HALO, C), _F32)

    # Rows past a sequence's end are only ever read by rows past it.
    _widen_product(bg_ref, x_ref, ze_ref, None, work)

    def piece(base, cs):
        rows = pl.ds(base, _CONV_CHUNK)
        c = _conv_taps(ze_ref, w_ref, None, base, cs, K)[1]
        y_ref[0, rows, cs] = (cg_ref[0, rows, cs].astype(_F32) * c
                              ).astype(y_ref.dtype)

    _each_piece(bs, C, piece, work=work)
    ze_ref[:_HALO] = ze_ref[bs:]    # the block behind this one reads them


def _bwd_kernel(bg_ref, cg_ref, x_ref, hbg_ref, hx_ref, w_ref, dy_ref, dp_ref,
                dw_ref, ze_ref, dc_ref, acc_ref, *, K, S, work):
    bs, C = bg_ref.shape[1:]
    b, n = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1) - 1 - n             # the block, last first
    ragged = S % bs != 0
    ze_ref[:_HALO] = jnp.where(  # zeros before a sequence's first block
        nb == 0, 0.0, hbg_ref[0].astype(_F32) * hx_ref[0].astype(_F32))
    _widen_product(bg_ref, x_ref, ze_ref, S - nb * bs if ragged else None,
                   work)

    @pl.when(n == 0)
    def _():  # nothing follows a sequence's last block
        dc_ref[bs:] = jnp.zeros((_HALO, C), _F32)

    @pl.when((b == 0) & (n == 0))
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def piece(base, cs):
        rows = pl.ds(base, _CONV_CHUNK)
        at = lambda k: pl.ds(k * C + cs.start, cs.size)
        part = lambda ref: ref[0, rows, cs].astype(_F32)
        taps, c = _conv_taps(ze_ref, w_ref, None, base, cs, K)
        dy = part(dy_ref)
        dc = dy * part(cg_ref)
        if ragged:  # rows past the sequence's end: nothing, whatever is there
            dc = jnp.where(_iota(dc.shape, 0) + (nb * bs + base) < S, dc, 0.0)
        dc_ref[rows, cs] = dc
        for j in range(K):
            acc_ref[j, :, cs] += _fold8(dc * taps[j])
        dz = _tree_sum([  # dz_t = sum_j w[j] dc_{t+K-1-j}
            _rows_at(dc_ref, base, cs, K - 1 - j)
            * w_ref[j:j + 1, cs].astype(_F32) for j in range(K)])
        dp_ref[0, rows, at(0)] = (dz * part(x_ref)).astype(dp_ref.dtype)
        dp_ref[0, rows, at(1)] = (dy * c).astype(dp_ref.dtype)
        dp_ref[0, rows, at(2)] = (dz * part(bg_ref)).astype(dp_ref.dtype)

    _each_piece(bs, C, piece, last_first=True, work=work)
    dc_ref[bs:] = dc_ref[:_HALO]    # the block before this one reads them

    @pl.when((b == pl.num_programs(0) - 1) & (n == pl.num_programs(1) - 1))
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)
        for j in range(K):
            dw_ref[j:j + 1, :] = jnp.sum(acc_ref[j], 0, keepdims=True)


def _blocks(p, w, rows):
    (B, S, _), (K, C) = p.shape, w.shape
    assert p.shape[2] == 3 * C and C % 128 == 0 and K <= 7, (p.shape, w.shape)
    bs = min(rows, -(-S // _HALO) * _HALO)
    work = next(c for c in _CONV_COLS if C % c == 0)
    return B, S, C, K, bs, work, -(-S // bs)


def _specs(bs, C, at):
    """BlockSpecs over grid (batch, row block), `at` the grid's row index
    to the block's: `rows(cols, part)`, a block's rows of `cols` columns,
    column block `part`; `halo(cols, part)`, the `_HALO` rows before them;
    `taps(r)`, r rows of [*, C]."""
    hb = bs // _HALO
    spec = lambda r, f: lambda cols, part=0: pl.BlockSpec(
        (1, r, cols), lambda i, n: (i, f(at(n)), part))
    return (spec(bs, lambda n: n),
            spec(_HALO, lambda n: jnp.maximum(n * hb - 1, 0)),
            lambda r: pl.BlockSpec((r, C), lambda i, n: (0, 0)))


@jax.jit
def _fwd_call(p, w):
    """p [B, S, 3C], w [K, C] -> y [B, S, C] in p's dtype."""
    B, S, C, K, bs, work, N = _blocks(p, w, ROWS[0])
    rows, _, taps = _specs(bs, C, lambda n: n)
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_fwd_kernel, K=K, work=work),
            grid=(B, N),
            in_specs=[rows(C, 0), rows(C, 1), rows(C, 2), taps(K)],
            out_specs=rows(C),
            out_shape=jax.ShapeDtypeStruct((B, S, C), p.dtype),
            scratch_shapes=[pltpu.VMEM((_HALO + bs, C), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=dispatch.interpret(),
        )(p, p, p, w)


@jax.jit
def _bwd_call(p, w, dy):
    """-> dp [B, S, 3C] in p's dtype and one float32 [8, C], dw's K rows."""
    B, S, C, K, bs, work, N = _blocks(p, w, ROWS[1])
    rows, halo, taps = _specs(bs, C, lambda n: N - 1 - n)
    parts = lambda spec: [spec(C, k) for k in range(3)]
    with jax.named_scope(SCOPE):
        return pl.pallas_call(
            functools.partial(_bwd_kernel, K=K, S=S, work=work),
            grid=(B, N),
            in_specs=parts(rows) + [halo(C, 0), halo(C, 2), taps(K), rows(C)],
            out_specs=[rows(3 * C), taps(8)],
            out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                       jax.ShapeDtypeStruct((8, C), _F32)],
            scratch_shapes=[pltpu.VMEM((_HALO + bs, C), _F32),
                            pltpu.VMEM((bs + _HALO, C), _F32),
                            pltpu.VMEM((K, 8, C), _F32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=dispatch.interpret(),
        )(p, p, p, p, p, w, dy)


@jax.custom_vjp
def gated_conv_pallas(p: jax.Array, w: jax.Array) -> jax.Array:
    """`gated_conv_xla` as the kernel pair (module docstring). The
    dispatcher comes here on the TPU; tests come here directly and run the
    kernels in interpret mode."""
    return _fwd_call(p, w)


def _vjp_fwd(p, w):
    return _fwd_call(p, w), (p, w)


def _vjp_bwd(res, dy):
    p, w = res
    dp, dw = _bwd_call(p, w, dy)
    return dp, dw[:w.shape[0]].astype(w.dtype)


gated_conv_pallas.defvjp(_vjp_fwd, _vjp_bwd)


def gated_conv_xla(p: jax.Array, w: jax.Array) -> jax.Array:
    """The same function in plain XLA, in p's dtype."""
    C = w.shape[1]
    bg, cg, x = (p[..., k * C:(k + 1) * C] for k in range(3))
    return cg * short_conv(bg * x, w)


def use_kernels(platform: str, channels: int, on_mesh: bool) -> bool:
    """The dispatch rule, a pure function of what the code observes: the
    kernels where a Mosaic call can run (`dispatch.mosaic`), with the
    channels whole 128-lane tiles."""
    return dispatch.mosaic(platform, on_mesh) and dispatch.whole(channels)


def gated_conv(p: jax.Array, w: jax.Array) -> jax.Array:
    """p [B, S, 3C] = [Bg ; Cg ; x], w [K, C] -> Cg * conv_K(Bg * x)
    [B, S, C], under the device scope `shortconv.core` (the backward kernel
    opens it itself: a backward rule is traced outside the mixer). The
    Pallas pair where `use_kernels` says so, else `gated_conv_xla`. Each
    traced call counts once in the phase table as `shortconv.core.pallas`
    or `shortconv.core.xla` with what it observed."""
    s = dispatch.site()
    (B, S, _), (K, C) = p.shape, w.shape
    kernels = use_kernels(s.platform, C, s.on_mesh)
    dispatch.observe("shortconv.core", kernels, batch=B, rows=S, channels=C,
                     taps=K)
    with jax.named_scope(SCOPE):
        return (gated_conv_pallas if kernels else gated_conv_xla)(p, w)
