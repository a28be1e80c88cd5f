"""Mixture-of-Experts layer + expert parallelism (SURVEY §5.7; ops/moe.py
GShard capacity-based dispatch), and a held range of experts: its windows,
row blocks and grouped-product kernels. The held layer against a masked
loop at every share and routing is tests/test_moe_held_loop.py."""
import jax
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import moe_tiny
from ray_tpu.parallel import MeshSpec, RULES_TP, make_mesh
from ray_tpu.train.step import transformer_train_step


def _tokens(cfg, batch=4, seq=32, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def test_moe_forward_and_grads():
    cfg = moe_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg)}
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, cfg)))(params)
    assert np.isfinite(float(loss))
    # Routed experts receive gradient (capacity>0 ensures some dispatch).
    g = np.asarray(grads["layers"]["moe_w_gate_up"])
    assert np.abs(g).sum() > 0
    # Router learns too.
    assert np.abs(np.asarray(grads["layers"]["router"])).sum() > 0


def test_moe_aux_loss_nonzero():
    cfg = moe_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    _, aux = tfm.forward_with_aux(params, _tokens(cfg), cfg)
    # Switch aux is ~1.0 at uniform routing; 0 would mean it's disconnected.
    assert 0.1 < float(aux) / cfg.n_layers < 10.0


def test_moe_trains():
    cfg = moe_tiny()
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    ts = transformer_train_step(cfg, mesh, rules=RULES_TP)
    params, opt = ts.init(jax.random.key(0))
    b = ts.shard_batch({"tokens": _tokens(cfg, batch=8)})
    losses = []
    for _ in range(5):
        params, opt, loss = ts.step(params, opt, b)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_expert_parallel_matches_single_device():
    """expert=2 mesh (all-to-all dispatch emitted by GSPMD) matches the
    single-device numerics."""
    cfg = moe_tiny()
    params = tfm.init_params(jax.random.key(0), cfg)
    batch = {"tokens": _tokens(cfg, batch=8)}
    ref = float(tfm.loss_fn(params, batch, cfg))

    mesh = make_mesh(MeshSpec(expert=2, data=2), devices=jax.devices()[:4])
    from ray_tpu.parallel import sharding as shd

    with shd.sharding_ctx(mesh, RULES_TP):
        ep = float(jax.jit(
            lambda p, b: tfm.loss_fn(p, b, cfg))(params, batch))
    assert abs(ep - ref) < 2e-3, (ep, ref)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_softmax_route_is_top_k_of_the_softmax(k):
    """`softmax_route`: softmax over every expert's score, the top k of the
    probabilities, renormalised: weights sum to 1, ids and weights are
    `jax.lax.top_k`'s of the softmax, and no bias or scale takes part."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    x = jax.random.normal(jax.random.key(0), (96, 32))
    w = jax.random.normal(jax.random.key(1), (32, 16)) * 0.3
    idx, wts = moe.softmax_route(x, w, experts_per_token=k)
    assert idx.shape == wts.shape == (96, k) and idx.dtype == jnp.int32
    np.testing.assert_allclose(wts.sum(-1), 1.0, atol=1e-6)
    p = jax.nn.softmax(jnp.dot(x, w, precision="highest"), -1)
    top, want = jax.lax.top_k(p, k)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(wts, top / top.sum(-1, keepdims=True),
                               atol=1e-6)


def test_held_layer_takes_the_routing_it_is_given():
    """`moe_ffn_held` with `softmax_route` bound: every expert held, the
    layer is the plain sum over each token's top k; the GShard path's
    capacity plays no part (nothing dropped at any skew)."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (2, 24, 16))
    rw = jax.random.normal(ks[1], (16, 8))
    wgu = jax.random.normal(ks[2], (8, 16, 2, 12)) * 0.2
    wd = jax.random.normal(ks[3], (8, 12, 16)) * 0.2
    route = functools.partial(moe.softmax_route, experts_per_token=2)
    with jax.default_matmul_precision("highest"):
        y, cnt = moe.moe_ffn_held(x, rw, wgu, wd, route=route,
                                  dtype=jnp.float32)
        xf = x.reshape(-1, 16)
        idx, wts = route(xf, rw)
        want = jnp.zeros_like(xf)
        for e in range(8):
            we = jnp.sum(jnp.where(idx == e, wts, 0.0), -1)
            h = jax.nn.silu(xf @ wgu[e, :, 0]) * (xf @ wgu[e, :, 1])
            want = want + we[:, None] * (h @ wd[e])
    np.testing.assert_allclose(y.reshape(-1, 16), want, atol=2e-5)
    assert float(cnt["dropped"]) == 0.0
    assert float(cnt["assigned"]) == 48 * 2


def _held_case(share, routing, kind, T=256, d=16, E=64, F=8, k=2):
    """A layer that holds E / share experts, and a routing: `even` (what a
    random router gives), `all_held` (every assignment on the held range)
    or `none_held`: every token carries a constant feature that the held
    experts' router columns weigh by +-30."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    Eh = E // share
    first = 0 if share == 1 else (E // 4 if share == 4 else 6)
    ks = jax.random.split(jax.random.key(share), 5)
    x = jax.random.normal(ks[0], (2, T // 2, d)).at[..., 0].set(1.0)
    push = {"even": 0.0, "all_held": 30.0, "none_held": -30.0}[routing]
    rw = (jax.random.normal(ks[1], (d, E)) * 0.3).at[
        0, first:first + Eh].add(push)
    wgu = jax.random.normal(ks[2], (Eh, d, 2, F)) * 0.3
    wd = jax.random.normal(ks[3], (Eh, F, d)) * 0.3
    wy = jax.random.normal(ks[4], x.shape)
    if kind == "sigmoid":  # saturated scores tie: the selection bias decides
        route = functools.partial(
            moe.sigmoid_route, experts_per_token=k, routed_scale=2.446,
            bias=jnp.linspace(-0.05, 0.05, E).at[first:first + Eh].add(
                push / 10))
    else:
        route = functools.partial(moe.softmax_route, experts_per_token=k)
    return (x, rw, wgu, wd), wy, route, first


def _held_loop(x, rw, wgu, wd, *, route, first):
    """The held experts' part as a plain masked loop over them."""
    import jax.numpy as jnp

    xf = x.reshape(-1, x.shape[-1])
    idx, wts = route(xf, rw)
    y = jnp.zeros_like(xf)
    for e in range(wgu.shape[0]):
        we = jnp.sum(jnp.where(idx == first + e, wts, 0.0), -1)
        h = jax.nn.silu(xf @ wgu[e, :, 0]) * (xf @ wgu[e, :, 1])
        y = y + we[:, None] * (h @ wd[e])
    return y.reshape(x.shape)


def _loss_and_grads(fn, args, wy):
    import jax.numpy as jnp

    def loss(*a):
        y, cnt = fn(*a)
        return jnp.sum(y * wy), (y, cnt)

    (_, (y, cnt)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(*args)
    return y, cnt, grads


def _assert_close(got, want, what):
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(got, want, atol=3e-5 * scale, err_msg=what)


def _given_route(kind, ids):
    """The routing GIVEN (`ids` [T, k]), the weights the router's: the
    scores of `sigmoid_route` / `softmax_route` at those experts,
    renormalised (and scaled), so that a held total is exact."""
    import jax.numpy as jnp

    def route(x, rw):
        z = jnp.dot(x.astype(jnp.float32), rw.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(z) if kind == "sigmoid" else jax.nn.softmax(z, -1)
        w = jnp.take_along_axis(s, ids, axis=-1)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        return ids, w * 2.446 if kind == "sigmoid" else w

    return route


def _given_ids(held, T=256, k=2, E=64, first=16, Eh=16):
    """[T, k] expert ids: `held` of the T k assignments on the held range."""
    import jax.numpy as jnp

    rng = np.random.RandomState(held)
    ids = rng.randint(0, E - Eh, T * k)
    ids = np.where(ids >= first, ids + Eh, ids)
    ids[rng.permutation(T * k)[:held]] = first + rng.randint(0, Eh, held)
    return jnp.asarray(ids.reshape(T, k), jnp.int32)


# 256 tokens x 2, 16 of 64 experts held: a first window of 384 rows in blocks
# of 128, then further ones of 256 rows (one block each: a tile of 256).
HELD_TOTALS = [0, 1, 127, 128, 129, 383, 384, 385, 512]


@pytest.mark.parametrize("kind", ["sigmoid", "softmax"])
@pytest.mark.parametrize("held", HELD_TOTALS)
def test_row_passes_work_the_blocks_the_held_rows_reach(held, kind):
    """The row passes of a window run over row blocks, as many as the held
    rows reach into, and what they leave untouched changes nothing: output,
    every gradient and every counter at a held total of zero, of one row, of
    a block exactly and a row either side, of the whole window and a row
    either side, and of every assignment there is, against the masked loop."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    T, k, E, first, Eh = 256, 2, 64, 16, 16
    args, wy, _, _ = _held_case(4, "even", kind)
    route = _given_route(kind, _given_ids(held))
    with jax.default_matmul_precision("highest"):
        y, cnt, grads = _loss_and_grads(functools.partial(
            moe.moe_ffn_held, route=route, held_first=first,
            dtype=jnp.float32), args, wy)
        want = _loss_and_grads(
            lambda *a: (_held_loop(*a, route=route, first=first), {}),
            args, wy)
    _assert_close(y, want[0], "output")
    for name, g, w in zip(("x", "router", "gate_up", "down"), grads, want[2]):
        _assert_close(g, w, name)
    rows = moe.held_window_rows(T, k, E, Eh)
    more = moe.further_window_rows(rows)
    assert (rows, more) == (384, 256)
    assert (moe.block_rows(rows), moe.block_rows(more)) == (128, 256)
    trips = 1 + max(-(-(held - rows) // more), 0)
    worked = -(-min(held, rows) // 128) * 128 + (trips - 1) * more
    got = {n: float(v) for n, v in cnt.items()}
    assert got == {
        "assigned": held, "dropped": 0.0, "past_buffer": max(held - rows, 0),
        "trips": trips, "window_rows": rows, "rows_worked": worked,
        "load_max": got["load_max"], "load_mean": held / Eh}
    # Whole blocks, the held rows at least, under a block a window more.
    assert held <= worked < held + 128 + (trips - 1) * more
    if not held:
        assert not np.any(np.asarray(y)) and worked == 0.0


@pytest.mark.parametrize("rows,block", [
    (81920, 4096), (40960, 2048),  # mellum2's first window, a further one
    (30720, 1536), (25600, 1024),  # kanana's, qwen3_next's (50 tiles: 2 each)
    (8192, 512), (4096, 512),      # the hybrid's
    (12800, 512),                  # 25 tiles
    (384, 128), (256, 256), (640, 128), (64, 2), (96, 4)])
def test_block_rows(rows, block):
    """A block of the row passes: whole row tiles of the grouped products
    (512, 256 or 128 rows as `_tiles` picks them; single rows where the
    window is not made of tiles), a twentieth of the window or the nearest
    below that divides it."""
    from ray_tpu.ops import moe

    assert moe.block_rows(rows) == block and rows % block == 0
    if rows % 128 == 0:
        assert block % moe._tiles(rows, 128, 128)[0] == 0
    assert moe.block_rows(rows, 1) == rows


@pytest.mark.parametrize("shape,rows", [
    ((16384, 8, 64, 16), 81920),   # mellum2's cell: 2.5 of 32,768
    ((8192, 8, 256, 8), 8192),     # the hybrid's: a row a token, not 5,120
    ((8192, 8, 256, 256), 65536),  # every expert held: every assignment
    ((256, 2, 64, 2), 256), ((300, 2, 64, 2), 384), ((64, 1, 8, 4), 64)])
def test_first_window_rows(shape, rows):
    """The first window: 2.5 of the held experts' even share, no fewer than
    a row a token (what one held expert can be given), to 128 rows, at most
    every assignment; the further ones half of it."""
    from ray_tpu.ops import moe

    assert moe.held_window_rows(*shape) == rows
    assert moe.further_window_rows(rows) == min(rows, -(-rows // 256) * 128)


@pytest.mark.parametrize("share,routing,factor,T", [
    (4, "even", None, 256), (4, "all_held", 2.5, 224), (32, "even", None, 256),
    (32, "none_held", None, 256), (4, "none_held", None, 256),
    (4, "all_held", None, 256), (4, 1, None, 256), (4, 129, None, 256),
    (4, 385, None, 256)])
def test_rows_in_no_group_may_hold_anything(share, routing, factor, T,
                                            monkeypatch):
    """A grouped product leaves the rows past its groups undefined on the
    TPU (on the CPU they come back zero), and the row passes' buffers start
    uninitialised (`lax.empty`) and are worked as far as the held rows reach:
    with NaN in every row of every [W, .] buffer that no pass has written
    (past the groups' end in a product's output, forward and transposed;
    everywhere in a fresh buffer), output and gradients are what they are
    without. (At factor 2.5 the 448 held assignments of 224 tokens take a
    window of 384 rows and a quarter of a further one of 256; a `routing`
    that is a number is that held total, given.)"""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    if factor:
        monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", factor)
    given = not isinstance(routing, str)
    args, wy, route, first = _held_case(share, "even" if given else routing,
                                        "softmax", T=T)
    if given:
        route = _given_route("softmax", _given_ids(routing))
    fn = functools.partial(moe.moe_ffn_held, route=route, held_first=first,
                           dtype=jnp.float32)
    want = _loss_and_grads(fn, args, wy)
    real, poisoned = jax.lax.ragged_dot, []

    def ragged_dot(lhs, rhs, group_sizes, *a, **kw):
        out = real(lhs, rhs, group_sizes, *a, **kw)
        poisoned.append(out.shape)
        in_a_group = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(in_a_group[:, None], out, jnp.nan)

    def empty(shape, dtype):
        poisoned.append(shape)
        return jnp.full(shape, jnp.nan, dtype)

    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    monkeypatch.setattr(jax.lax, "empty", empty)
    y, cnt, grads = _loss_and_grads(fn, args, wy)
    # Both products, both transposed to rows, and three fresh buffers.
    assert len(poisoned) >= 7
    rows = int(cnt["window_rows"])
    more = moe.further_window_rows(rows)
    held, trips = float(cnt["assigned"]), float(cnt["trips"])
    assert held < rows + (trips - 1) * more  # some rows are in no group
    assert trips == 1 + max(-(-(int(held) - rows) // more), 0)
    assert float(cnt["rows_worked"]) < held + moe.block_rows(rows) + (
        trips - 1) * moe.block_rows(more)
    for got, w in zip((y,) + grads, (want[0],) + want[2]):
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, w, atol=1e-6)


@pytest.mark.parametrize("routing", ["even", "all_held"])
def test_grouped_product_kernels_match_ragged_dot(routing, monkeypatch):
    """The Pallas grouped matmul (the chip's path, here in interpret mode)
    and `lax.ragged_dot` (the CPU's) give the held layer the same output
    and gradients on bfloat16 operands: the first window through the
    kernels and, with every assignment on the held quarter, two further ones
    (which stay `ragged_dot`'s) behind it."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", 1.25)  # 256 + 2 x 128 rows
    args, wy, route, first = _held_case(4, routing, "softmax", d=128, E=16,
                                        F=128)
    args = (args[0].astype(jnp.bfloat16),) + args[1:]
    fn = functools.partial(moe.moe_ffn_held, route=route, held_first=first)
    want = _loss_and_grads(fn, args, wy)
    assert not moe.use_kernels("cpu", jnp.bfloat16, (128, 128), False)
    assert not moe.use_kernels("tpu", jnp.bfloat16, (128, 128), True)
    assert not moe.use_kernels("tpu", jnp.float32, (128, 128), False)
    assert not moe.use_kernels("tpu", jnp.bfloat16, (128, 96), False)
    assert moe.use_kernels("tpu", jnp.bfloat16, (128, 128), False)
    monkeypatch.setattr(moe, "use_kernels", lambda *a: True)
    y, cnt, grads = _loss_and_grads(fn, args, wy)
    assert float(cnt["trips"]) == (1 if routing == "even" else 3)
    for got, w in zip((y,) + grads, (want[0],) + want[2]):
        got, w = np.asarray(got, np.float32), np.asarray(w, np.float32)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, w, atol=2e-2 * np.max(np.abs(w)))


# (tokens, window rows, held rows, the held rows' tokens): 512 tokens in
# blocks of 128, rows of 128 bfloat16 in product tiles of 256.
WINDOW_SUMS = {
    "duplicates": (512, 1024, 600, lambda rng: rng.randint(0, 64, 600)),
    "a_block_with_no_row": (512, 1024, 300, lambda rng: np.where(
        rng.rand(300) < 0.5, rng.randint(0, 128, 300),
        rng.randint(384, 512, 300))),
    "under_a_tile": (512, 1024, 5, lambda rng: rng.randint(0, 512, 5)),
    "a_tile_and_a_row": (512, 1024, 257, lambda rng: rng.randint(0, 512, 257)),
    "whole_window": (512, 1024, 1024, lambda rng: rng.randint(0, 512, 1024)),
    "nothing_held": (512, 1024, 0, lambda rng: np.zeros(0, np.int64)),
}


@pytest.mark.parametrize("case", list(WINDOW_SUMS) + ["layer"])
def test_window_sum_as_a_grouped_product_is_the_scatter_add(case, monkeypatch):
    """A first window's sum back to the tokens as `token_order` +
    `block_sums` (the chip's path, the Pallas grouped product here in
    interpret mode) against `scatter_rows` on the same (tok, rows, held): a
    token with many rows, token blocks with none (zeros, not what a buffer
    held), fewer held rows than a tile, a tile and a row, every row held,
    none; NaN stands in every row past the held ones and none reaches a sum;
    the sums agree to one rounding of bfloat16. `layer`: the layer's output
    and `jax.grad` with the product against the same kernels with the
    scatter-add (a block the tokens are no multiple of), and what each
    counts where it is traced."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe
    from ray_tpu.util import tracing

    if case != "layer":
        T, W, held, tokens = WINDOW_SUMS[case]
        rng = np.random.RandomState(held)
        tok = np.full(W, T, np.int32)
        tok[:held] = tokens(rng)
        rows = rng.standard_normal((W, 128)).astype(np.float32)
        rows[held:] = np.nan
        tok, rows = jnp.asarray(tok), jnp.asarray(rows, jnp.bfloat16)
        want = np.asarray(jnp.zeros((T, 128), jnp.float32).at[tok].add(
            jnp.nan_to_num(rows.astype(jnp.float32)), mode="drop"))
        by, tok_t, sizes = moe.token_order(tok, T, 128)
        assert int(sizes.sum()) == held and np.all(np.diff(tok_t) >= 0)
        assert np.array_equal(tok_t, tok[by])
        assert np.array_equal(by, np.argsort(tok, kind="stable"))
        got = np.asarray(moe.block_sums(
            tok_t, rows[by], sizes, T, 128,
            tiling=lambda m, k, n: (256, 128, 128)), np.float32)
        was = np.asarray(moe.scatter_rows(tok, rows, held, T), np.float32)
        assert np.all(np.isfinite(got))
        assert not np.any(got[np.setdiff1d(np.arange(T), tok[:held])])
        # float32 sums rounded once: half a unit in bfloat16's last place
        np.testing.assert_allclose(got, want, rtol=2 ** -8, atol=1e-6)
        # (the CPU's scatter-add rounds every addition)
        np.testing.assert_allclose(was, want,
                                   atol=2 ** -6 * (1 + np.max(np.abs(want))))
        return
    monkeypatch.setattr(moe, "HELD_WINDOW_FACTOR", 1.25)
    args, wy, route, first = _held_case(4, "even", "softmax", d=128, E=16,
                                        F=128)
    args = (args[0].astype(jnp.bfloat16),) + args[1:]
    fn = functools.partial(moe.moe_ffn_held, route=route, held_first=first)
    monkeypatch.setattr(moe, "use_kernels", lambda *a: True)
    count = lambda how: tracing.phase_table().get(
        "train.moe_combine." + how, {}).get("count", 0)
    outs = {}
    for how, block in (("scatter", 96), ("product", 128)):
        monkeypatch.setattr(moe, "TOKEN_BLOCK", block)  # 256 tokens
        other = "scatter" if how == "product" else "product"
        before = count(how), count(other)
        outs[how] = _loss_and_grads(fn, args, wy)
        assert count(how) >= before[0] + 2  # the combine and dx
        assert count(other) == before[1]
    (y, cnt, grads), (y0, cnt0, grads0) = outs["product"], outs["scatter"]
    assert float(cnt["trips"]) == 1.0 and float(cnt["dropped"]) == 0.0
    for got, w in zip((y,) + grads, (y0,) + grads0):
        got, w = np.asarray(got, np.float32), np.asarray(w, np.float32)
        assert np.all(np.isfinite(got))
        np.testing.assert_allclose(got, w, atol=2 ** -7 * np.max(np.abs(w)))
