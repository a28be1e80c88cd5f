"""The one backward kernel of ops/flash_attention.py (interpret mode on the
CPU): against the dQ and dK/dV kernels it replaced and against XLA's autodiff
of the plain attention, the rule that picks it from the shapes, what
`flash.plan` says of it, and the pair's jaxpr held to the parent's. A file of
its own: `--dist loadfile` gives a file to one worker, and these cases trace
three gradient programs each."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention, tile_sizes
from test_flash_attention import _BAND, _rand_qkv

_BF16 = jnp.bfloat16
# (S, H, KVH, D, Dv, causal, window, dtype, tiles): float32 with small named
# tiles reaches every body of the backward (interior strips, the diagonal's
# cells, a band's cells, whole masked tiles of a ragged or oblong grid);
# bfloat16 with nothing named lands on `_TILES`' four rows.
_FUSED = [
    (384, 2, 2, 64, 64, True, None, jnp.float32, _BAND),
    (1024, 2, 1, 32, 32, True, 512, jnp.float32,
     dict(block_q=256, block_k=256, sub=64)),
    (2048, 2, 1, 128, 128, True, 1024, _BF16, {}),   # a band inside a block
    (128, 8, 1, 64, 64, True, None, jnp.float32,
     dict(block_q=64, block_k=64, sub=32)),
    (128, 9, 1, 64, 64, True, 50, jnp.float32,
     dict(block_q=64, block_k=64, sub=32)),
    (300, 2, 1, 64, 64, True, None, jnp.float32, _BAND),
    (300, 2, 1, 64, 32, False, None, jnp.float32,
     dict(block_q=128, block_k=64, sub=32)),
    (300, 2, 1, 64, 32, False, None, jnp.float32,
     dict(block_q=64, block_k=128, sub=32)),
    (300, 2, 1, 32, 32, True, 70, jnp.float32, _BAND),
    (1024, 2, 2, 64, 64, True, None, _BF16, {}),
    (2048, 2, 1, 128, 128, True, None, _BF16, {}),
    (2048, 2, 2, 192, 128, True, None, _BF16, {}),
    (2048, 2, 1, 256, 256, True, None, _BF16, {}),
]


def _flash_grads(q, k, v, w, **kw):
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **kw).astype(jnp.float32) * w)
    return jax.grad(loss, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize(
    "S,H,KVH,D,Dv,causal,window,dtype,tiles", _FUSED,
    ids=[f"S{c[0]}-H{c[1]}_{c[2]}-D{c[3]}_{c[4]}-"
         f"{'causal' if c[5] else 'full'}-W{c[6]}-"
         f"{'bf16' if c[7] == _BF16 else 'f32'}-"
         f"{c[8].get('block_q')}_{c[8].get('block_k')}" for c in _FUSED])
def test_fused_backward_against_the_pair_and_autodiff(
        monkeypatch, S, H, KVH, D, Dv, causal, window, dtype, tiles):
    """The one backward kernel (dK, dV and dQ from one S, dP and
    exponential) against the dQ and dK/dV kernels it replaces, which a
    budget of nothing still selects, and against XLA's autodiff of the
    plain attention: full causal, windows of 512 and 1,024, 8 and 9 query
    heads a key head, a ragged last block on either side, the table's four
    head widths."""
    q, k, v = _rand_qkv(jax.random.key(S + D), 1, S, H, KVH, D, dtype, Dv)
    w = jax.random.normal(jax.random.key(3), (1, S, H, Dv))
    kw = dict(causal=causal, window=window, **tiles)
    assert fa.bwd_kind(S, D, Dv, dtype, **tiles) == "fused"
    fused = _flash_grads(q, k, v, w, **kw)
    monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", 0)
    assert fa.bwd_kind(S, D, Dv, dtype, **tiles) == "split"
    pair = _flash_grads(q, k, v, w, **kw)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
        q, k, v, causal=causal, window=window) * w), (0, 1, 2))(*f32)
    # Against the pair only the order of dQ's float32 sums differs: one
    # rounding of the result in bfloat16. Against autodiff as the shapes'
    # test: P and dS are rounded to 2^-8 before their products.
    near, far = (2.0 ** -7, 2e-2) if dtype == _BF16 else (1e-5, 1e-4)
    for name, a, b, c in zip(("dq", "dk", "dv"), fused, pair, want):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        top = max(float(np.max(np.abs(np.asarray(c)))), 1.0)
        assert a.shape == c.shape and np.isfinite(a).all(), name
        assert np.max(np.abs(a - b)) <= near * top, (name, "pair")
        assert np.max(np.abs(a - np.asarray(c))) <= far * top, (name, "ref")


@pytest.mark.parametrize("S,D,Dv,dtype,kind", [
    (16384, 192, 128, _BF16, "fused"),   # kanana: 16 + 16 MiB
    (16384, 256, 256, _BF16, "fused"),
    (16384, 128, 128, _BF16, "fused"),
    (1024, 64, 64, _BF16, "fused"),
    (32768, 192, 128, _BF16, "fused"),   # 8 M elements in whole lanes:
    (65536, 128, 128, _BF16, "fused"),   # the budget to the byte
    (65536, 192, 128, _BF16, "split"),
    (131072, 128, 128, _BF16, "split"),
    (16384, 256, 256, jnp.float32, "fused"),
    (32768, 256, 256, jnp.float32, "split"),
])
def test_backward_kind_follows_the_heads_bytes(S, D, Dv, dtype, kind):
    """Fused where a head's float32 dQ accumulator and dQ's two output
    buffers fit `FUSED_DQ_VMEM_BUDGET` (D in whole lanes of 128), the pair
    beyond it; from the shapes alone."""
    assert fa.bwd_kind(S, D, Dv, dtype) == kind
    bq = tile_sizes(S, D, Dv, dtype)[0]
    fits = fa._dq_head_bytes(S, bq, D, jnp.dtype(dtype).itemsize) <= (
        fa.FUSED_DQ_VMEM_BUDGET)
    assert fits == (kind == "fused")


@pytest.mark.parametrize("budget,kind,calls", [(None, "fused", 2),
                                               (1 << 16, "split", 3)])
def test_flash_plan_says_which_backward(monkeypatch, budget, kind, calls):
    """The forward's `flash.plan` observation carries `bwd`, the traced
    backward counts itself as `flash.plan.bwd_<kind>`, and the gradient's
    program holds that many kernel calls: a head past the budget (here a
    budget made small) takes the pair and says so."""
    from ray_tpu.util import tracing

    if budget is not None:
        monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", budget)
    seen = []
    observe = tracing.observe
    monkeypatch.setattr(tracing, "observe", lambda name, *a, **kw: (
        seen.append((name, kw)), observe(name, *a, **kw))[1])
    q, k, v = _rand_qkv(jax.random.key(7), 1, 256, 2, 2, 32)
    tiles = dict(block_q=128, block_k=128, sub=64)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **tiles)), (0, 1, 2)))(q, k, v)
    plans = [kw for name, kw in seen if name == "flash.plan"]
    assert [p["bwd"] for p in plans] == [kind]
    # two blocks a side: the folded grid's one line of three steps, all tiles
    assert [(p["grid_steps"], p["grid_steps_idle"]) for p in plans] == [(3, 0)]
    assert [n for n, _ in seen if n.startswith("flash.plan.bwd")] == [
        f"flash.plan.bwd_{kind}"]
    assert str(jaxpr).count("pallas_call") == calls
    assert f"flash.plan.bwd_{kind}" in tracing.phase_table()


def _pick(cond, a, b):
    return a if cond else b


@pytest.mark.parametrize("keys_inner", [True, False], ids=["keys", "queries"])
@pytest.mark.parametrize("n", range(1, 10))
def test_the_folded_grid_is_the_triangle_once(n, keys_inner):
    """`_fold` on plain ints: for an even n >= 2 the n / 2 x (n + 1) steps
    are every tile at or below the diagonal exactly once, a row's (a q
    block's keys, a key block's queries) steps next to each other with the
    inner block ascending, and the step and steps the kernels reset and
    flush by count that row; an odd n and n = 1 keep the rectangle."""
    b = 128
    folded = fa._folded(True, b, b, n * b)
    assert folded == (n >= 2 and n % 2 == 0)
    dims = fa._grid_dims(True, b, b, n * b, None, keys_inner)
    tiles = n * (n + 1) // 2
    if not folded:
        assert dims == (n, n)
        assert fa.grid_steps(n * b, b, b, True) == (n * n, n * n - tiles)
        return
    assert dims == (n // 2, n + 1)
    assert fa.grid_steps(n * b, b, b, True) == (tiles, 0)
    steps = [fa._fold(p, j, n, keys_inner, _pick)
             for p in range(dims[0]) for j in range(dims[1])]
    assert sorted((iq, ik) for iq, ik, _, _ in steps) == [
        (i, j) for i in range(n) for j in range(i + 1)]
    rows = []  # [(row, its inner blocks in grid order)]
    for iq, ik, step, count in steps:
        row, inner = (iq, ik) if keys_inner else (ik, iq)
        if not rows or rows[-1][0] != row:
            rows.append((row, []))
        assert step == len(rows[-1][1])          # 0 where the scratch is reset
        rows[-1][1].append(inner)
        assert count == (row + 1 if keys_inner else n - row)
    assert sorted(r for r, _ in rows) == list(range(n))  # a row is one run
    for row, inner in rows:  # ascending, and the last step is the flush's
        assert inner == (list(range(row + 1)) if keys_inner
                         else list(range(row, n)))
    # the fused backward writes dQ where a head's grid ends
    assert keys_inner or steps[-1][:2] == (n - 1, n // 2)


@pytest.mark.parametrize("causal,bq,bk,S,window,steps", [
    (True, 1024, 1024, 32768, None, (528, 0)),     # the sparse cell's core
    (True, 1024, 1024, 16384, None, (136, 0)),     # kanana's latent heads
    (True, 128, 128, 512, 100, (8, 1)),            # a window: its own grid
    (True, 128, 64, 512, None, (32, 12)),          # named unequal blocks
    (True, 128, 128, 500, None, (16, 6)),          # a ragged last block
    (False, 128, 128, 512, None, (16, 0)),         # not causal
])
def test_only_a_full_causal_square_even_grid_folds(causal, bq, bk, S, window,
                                                   steps):
    """The rule reads the static shapes: a window, unequal blocks, a ragged
    block or a call that is not causal keeps the parent's grid, with the
    steps it enters to do nothing counted."""
    assert fa._folded(causal, bq, bk, S, window) == (steps[0] in (528, 136))
    assert fa.grid_steps(S, bq, bk, causal, window) == steps


def _forward_and_backward(q, k, v, do, **tiles):
    """(o, lse, dq, dk, dv) of the kernels themselves, model layout in."""
    qt, kt, vt, dot = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, do))
    scale = q.shape[-1] ** -0.5
    o, lse = fa._flash_fwd(qt, kt, vt, scale, True, **tiles)
    delta = jnp.sum(dot.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    return (o, lse) + tuple(fa.flash_bwd_core(
        qt, kt, vt, dot, lse, delta, scale=scale, causal=True, **tiles))


@pytest.mark.parametrize("S,H,KVH,D,Dv,budget", [
    (512, 2, 2, 64, 64, None), (512, 4, 2, 64, 64, None),
    (512, 2, 2, 192, 128, None), (512, 4, 2, 192, 128, None),
    (512, 4, 2, 64, 64, 0),                        # the pair: dQ's own kernel
    (384, 2, 2, 64, 64, None), (384, 4, 2, 64, 64, None),
    (384, 2, 2, 192, 128, None), (384, 4, 2, 192, 128, None),
])
def test_the_folded_call_is_the_rectangular_one_bit_for_bit(
        monkeypatch, S, H, KVH, D, Dv, budget):
    """Blocks of 128: four a side fold, three keep the rectangle. Against
    XLA's autodiff of the plain attention, and against the SAME call on the
    rectangular grid (the rule answering no, here in the test): o, lse, dK
    and dV bit for bit, since a row's key blocks and a key block's query
    blocks still arrive in ascending order; dQ too from its own kernel, and
    within float32 rounding from the fused one, which sums a row's key
    blocks in the order the grid brings them."""
    if budget is not None:
        monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", budget)
    tiles = dict(block_q=128, block_k=128, sub=64)
    assert fa._folded(True, 128, 128, S) == (S == 512)
    q, k, v = _rand_qkv(jax.random.key(S + D + H), 1, S, H, KVH, D, Dv=Dv)
    do = jax.random.normal(jax.random.key(5), (1, S, H, Dv))
    run = lambda: jax.jit(lambda *a: _forward_and_backward(*a, **tiles))(
        q, k, v, do)
    got = rect = run()
    if S == 512:
        monkeypatch.setattr(fa, "_folded", lambda *a, **kw: False)
        rect = run()
    def plain(q, k, v, do):
        o, vjp = jax.vjp(lambda q, k, v: reference_attention(
            q, k, v, causal=True), q, k, v)
        return (o,) + vjp(do)

    want = jax.jit(plain)(q, k, v, do)
    want = (want[0], None) + want[1:]
    for name, a, b, c in zip(("o", "lse", "dq", "dk", "dv"), got, rect, want):
        if name == "dq" and budget is None:
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * float(
                jnp.max(jnp.abs(b))), name
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
        if c is not None:
            c = jnp.swapaxes(c, 1, 2)
            assert float(jnp.max(jnp.abs(a - c))) <= 1e-4 * max(
                float(jnp.max(jnp.abs(c))), 1.0), name


@pytest.mark.parametrize("S,H,KVH,D,Dv,causal,window,dtype,tiles,digest", [
    (256, 2, 1, 64, 64, True, None, jnp.float32, _BAND, "d9b14360bb2b"),
    (384, 2, 1, 64, 64, True, None, jnp.float32, _BAND, "6cb8e62316fb"),
    (300, 2, 1, 64, 32, False, None, jnp.float32,
     dict(block_q=128, block_k=64, sub=32), "cacd8dd52a30"),
    (512, 2, 1, 32, 32, True, 100, jnp.float32, _BAND, "a7cae943f607"),
    (2048, 2, 2, 192, 128, True, None, _BF16, {}, "851bfbbaf9fe"),
    (3072, 2, 2, 192, 128, True, None, _BF16, {}, "d8e29133de23"),
    (2048, 2, 1, 128, 128, True, 1024, _BF16, {}, "daf2d25493a3"),
])
def test_the_pair_traces_what_it_traced(monkeypatch, S, H, KVH, D, Dv, causal,
                                        window, dtype, tiles, digest):
    """Past the budget a gradient's jaxpr (forward, dQ and dK/dV kernels,
    bodies included) is the one PR 46's tree traced, by sha256 prefix: the
    fused backward's parts are traced only where it is taken. After a
    deliberate change to the forward or the pair, print the new ones. PR 57
    folded the grid of a full causal call with an even number of square
    blocks a side: the two such rows (256 at blocks of 128, 2,048 at the
    table's 1,024: two a side) are that tree's, and the rows beside them
    (three a side: the rectangle) were taken from ITS parent's tree and
    hold the rectangle to it."""
    import hashlib

    monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", 0)
    q, k, v = (jnp.zeros((1, S, h, d), dtype)
               for h, d in ((H, D), (KVH, D), (KVH, Dv)))
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal, window=window, **tiles
                        ).astype(jnp.float32)), (0, 1, 2)))(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == digest
