"""Flash-attention kernel vs the reference XLA implementation.

Runs the Pallas kernels in interpret mode on CPU (same code path the TPU
compiles), checking forward values and gradients, causal + GQA variants,
the tile plan and the windowed kernels. The kernels at the shapes `_TILES`
serves are tests/test_flash_attention_shapes.py.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention, tile_plan, tile_sizes


def _rand_qkv(key, B, S, H, KVH, D, dtype=jnp.float32, Dv=None):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, KVH, D), dtype)
    v = jax.random.normal(kv, (B, S, KVH, Dv or D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("H,KVH", [(4, 4), (4, 2)])
def test_forward_matches_reference(causal, H, KVH):
    B, S, D = 2, 256, 64
    q, k, v = _rand_qkv(jax.random.key(0), B, S, H, KVH, D)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_grad_matches_reference():
    B, S, H, KVH, D = 1, 128, 2, 1, 64
    q, k, v = _rand_qkv(jax.random.key(1), B, S, H, KVH, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_grad_gqa_group_sum():
    B, S, H, KVH, D = 1, 128, 4, 2, 32
    q, k, v = _rand_qkv(jax.random.key(2), B, S, H, KVH, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_uneven_blocks():
    # S not a multiple of the block: Pallas pads the trailing block.
    B, S, H, KVH, D = 1, 192, 2, 2, 64
    q, k, v = _rand_qkv(jax.random.key(3), B, S, H, KVH, D)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_uneven_blocks_grad():
    B, S, H, KVH, D = 1, 96, 2, 1, 32
    q, k, v = _rand_qkv(jax.random.key(4), B, S, H, KVH, D)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_runs_per_shard_on_a_multi_device_mesh(flash_kernels):
    """GSPMD cannot partition a Mosaic kernel (a hard lowering error on a
    real 2x2 mesh): under a multi-device sharding context the flash kernel
    runs per batch/head shard through shard_map, and agrees with the dense
    reference — values and gradients, heads over `tensor`, batch over the
    data axes."""
    import ray_tpu.ops.attention as att
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import DEFAULT_RULES, sharding_ctx

    mesh = make_mesh(MeshSpec(fsdp=2, tensor=2), devices=jax.devices()[:4])
    q, k, v = _rand_qkv(jax.random.key(5), 4, 64, 4, 2, 32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    def sharded(q, k, v):
        with sharding_ctx(mesh, DEFAULT_RULES):
            return att.attention(q, k, v, causal=True)

    out, grads = jax.jit(jax.value_and_grad(loss(sharded), (0, 1, 2)))(q, k, v)
    ref, rgrads = jax.value_and_grad(
        loss(lambda q, k, v: reference_attention(q, k, v, causal=True)),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=1e-4)
    for a, b in zip(grads, rgrads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)
    # The sharded program really went through shard_map.
    assert "shard_map" in str(jax.make_jaxpr(sharded)(q, k, v))


def _painted(S, plan, causal):
    """The S x S positions the plan's interior and edge cells cover, and the
    counts of each class, from the cells alone (no `_tile_class`)."""
    bq, bk, sub = plan.bq, plan.bk, plan.sub
    cq, ck = (sub, sub) if sub < bq else (bq, bk)
    g = math.gcd(S, cq, ck)  # painted in squares of g positions
    covered = np.zeros((S // g, S // g), bool)
    counts = dict(interior=0, edge=0, skipped=0)
    for r0 in range(0, S, cq):
        for c0 in range(0, S, ck):
            r1, c1 = r0 + cq - 1, c0 + ck - 1  # r1, c1 may pass S (ragged)
            if causal and c0 > r1:
                counts["skipped"] += 1
                continue
            whole = (not causal or c1 <= r0) and c0 + ck <= S
            counts["interior" if whole else "edge"] += 1
            covered[r0 // g:(r0 + cq) // g, c0 // g:(c0 + ck) // g] = True
    return covered, counts


@pytest.mark.parametrize("S,bq,bk,sub,causal", [
    (1024, 1024, 1024, 256, True), (1024, 512, 512, 128, True),
    (2048, 1024, 1024, 128, True), (8192, 1024, 1024, 256, True),
    (8192, 512, 512, 512, True), (1280, 512, 512, 512, True),
    (1024, 512, 512, 128, False), (1280, 512, 512, 256, False),
    (1024, 256, 512, 256, True)])
def test_tile_plan_counts_and_cover(S, bq, bk, sub, causal):
    plan = tile_plan(S, bq, bk, sub, causal)
    assert (plan.bq, plan.bk) == (bq, bk)
    covered, counts = _painted(S, plan, causal)
    assert (plan.tiles_interior, plan.tiles_edge, plan.tiles_skipped) == (
        counts["interior"], counts["edge"], counts["skipped"])
    # Interior + edge cells cover the causal triangle (or the square), and
    # no computed cell lies wholly outside it.
    r, c = np.indices(covered.shape)
    need = (r >= c) if causal else np.ones(covered.shape, bool)
    assert not (need & ~covered).any()
    if causal and bq == bk and S % bq == 0:
        # The closed form in cells of `sub` (n a side): the diagonal is edge,
        # the strict lower triangle interior, the strict upper one skipped.
        n = S // plan.sub
        assert (plan.tiles_interior, plan.tiles_edge, plan.tiles_skipped) == (
            n * (n - 1) // 2, n, n * (n - 1) // 2)
    if not causal:
        n_q, n_k = -(-S // bq), -(-S // bk)
        ragged = n_q if S % bk else 0
        assert (plan.tiles_interior, plan.tiles_edge, plan.tiles_skipped) == (
            n_q * n_k - ragged, ragged, 0)


def test_tile_sizes_follow_the_shapes():
    """The table's row where the widths and dtype are known and its block
    divides S, `DEFAULT_BLOCK` elsewhere; a named block wins; a strip that
    divides nothing is the whole block."""
    block, sub = fa._TILES[(64, 64)]
    assert tile_sizes(4 * block, 64, 64, jnp.bfloat16) == (block, block, sub)
    assert tile_sizes(block // 2, 64, 64, jnp.bfloat16)[:2] == (
        block // 2, block // 2)
    d = fa.DEFAULT_BLOCK
    assert tile_sizes(4 * d, 80, 80, jnp.bfloat16) == (d, d, d)
    assert tile_sizes(4 * d, 64, 64, jnp.float32) == (d, d, d)
    assert tile_sizes(2 * block + 128, 64, 64, jnp.bfloat16)[:2] == (d, d)
    assert tile_sizes(1024, 64, 64, jnp.bfloat16, 128, 128) == (
        128, 128, min(sub, 128))
    assert tile_sizes(192, 64, 64, jnp.float32, 96, 96, 64) == (96, 96, 96)


def test_flash_plan_in_the_phase_table():
    """A traced call leaves one `flash.plan` observation with the forward's
    tile counts (layers under one scan trace once)."""
    from ray_tpu.util import tracing

    before = tracing.phase_table().get("flash.plan", {}).get("count", 0)
    q, k, v = _rand_qkv(jax.random.key(7), 1, 256, 2, 2, 32)
    tiles = dict(block_q=128, block_k=128, sub=64)
    jax.jit(lambda q, k, v: flash_attention(q, k, v, **tiles)).lower(q, k, v)
    row = tracing.phase_table()["flash.plan"]
    assert row["count"] == before + 1
    plan = tile_plan(256, 128, 128, 64, True)
    assert plan == (128, 128, 64, 6, 4, 6)


# ------------------------------------------------------------ the window

# (S, window, tiles): blocks of 128 with strips of 32 unless named.
_BAND = dict(block_q=128, block_k=128, sub=32)
_WINDOWS = [
    (512, 16, _BAND),     # smaller than a strip
    (512, 100, _BAND),    # between a strip and a block
    (512, 128, _BAND),    # a block: two key blocks a query block
    (512, 129, _BAND),    # one key past a block: a third block's corner
    (512, 300, _BAND),    # several blocks: interior tiles inside the band
    (384, 1, _BAND),      # every query sees itself alone
    (512, 4096, _BAND),   # larger than the sequence: plain causal
    (500, 100, _BAND),    # a ragged length: whole masked tiles
    (500, 130, dict(block_q=128, block_k=64, sub=32)),  # and unequal blocks
    (512, 100, dict(block_q=128, block_k=128, sub=128)),  # no strips
]


@pytest.mark.parametrize("S,window,tiles", _WINDOWS,
                         ids=[f"S{s}-W{w}-{t['block_q']}_{t['block_k']}_"
                              f"{t['sub']}" for s, w, t in _WINDOWS])
def test_windowed_forward_and_gradients_match_reference(S, window, tiles):
    """Query i sees keys j with 0 <= i - j < window, in all three kernels:
    output and the gradients of q, k and v against
    `reference_attention(window=)`, float32, 2:1 grouped heads. A band off
    by one key is an error of order one."""
    q, k, v = _rand_qkv(jax.random.key(S + window), 1, S, 2, 1, 32)
    w = jax.random.normal(jax.random.key(3), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    flash = lambda q, k, v: flash_attention(q, k, v, window=window, **tiles)
    ref = lambda q, k, v: reference_attention(q, k, v, window=window)
    # (each a jitted program: op by op the small ops compile one at a time)
    both = lambda fn: [jax.jit(fn)(q, k, v), *jax.jit(jax.grad(
        loss(fn), (0, 1, 2)))(q, k, v)]
    got, want = both(flash), both(ref)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        err = float(jnp.max(jnp.abs(a - b)))  # dq, dk are 0 at one key
        assert err <= 1e-4 * max(float(jnp.max(jnp.abs(b))), 1.0), (name, err)
    # The reference's own band: one key fewer or more is another answer.
    off = reference_attention(q, k, v, window=window + 1)
    if window < S:
        assert float(jnp.max(jnp.abs(off - want[0]))) > 1e-3


@pytest.mark.parametrize("S,bq,bk,sub,window", [
    (2048, 512, 512, 128, 512), (2048, 512, 512, 128, 100),
    (2048, 512, 512, 128, 513), (2048, 512, 512, 128, 1300),
    (2048, 512, 512, 512, 512), (2048, 256, 512, 256, 300),
    (1280, 512, 512, 128, 200), (16384, 2048, 2048, 256, 1024)])
def test_tile_plan_with_a_window_against_a_brute_count(S, bq, bk, sub,
                                                       window):
    """Every cell of the plan classified from its (i, j) pairs alone:
    interior where all of them are in the band and the sequence, skipped
    where none is in the band, edge otherwise; and what the banded grid
    visits (`_band_steps`) covers every cell that is not skipped."""
    plan = tile_plan(S, bq, bk, sub, True, window)
    cq, ck = (plan.sub, plan.sub) if plan.sub < bq else (bq, bk)
    counts = dict(interior=0, edge=0, skipped=0)
    for r0 in range(0, -(-S // bq) * bq, cq):
        for c0 in range(0, -(-S // bk) * bk, ck):
            i = np.arange(r0, r0 + cq)[:, None]
            j = np.arange(c0, c0 + ck)[None, :]
            band = (i >= j) & (i - j < window)
            if not band.any():
                counts["skipped"] += 1
            elif band.all() and c0 + ck <= S:
                counts["interior"] += 1
            else:
                counts["edge"] += 1
    assert (plan.tiles_interior, plan.tiles_edge, plan.tiles_skipped) == (
        counts["interior"], counts["edge"], counts["skipped"])
    k_steps, q_steps = fa._band_steps(S, bq, bk, window)
    for iq in range(-(-S // bq)):
        first = max(iq * bq - (window - 1), 0) // bk
        for ik in range(-(-S // bk)):
            skipped = fa._tile_class(iq, ik, bq=bq, bk=bk, seq_len=S,
                                     causal=True, ragged="k",
                                     window=window)[0]
            assert skipped or first <= ik < first + k_steps, (iq, ik)
            assert skipped or (ik * bk) // bq <= iq < (
                ik * bk) // bq + q_steps, (iq, ik)
    if S == 16384:  # the cell's windowed layer: most of the grid is skipped
        assert plan.tiles_skipped > 10 * (plan.tiles_interior
                                          + plan.tiles_edge)
        assert (k_steps, q_steps) == (2, 2) and (bq, bk, sub) == tile_sizes(
            S, 128, 128, jnp.bfloat16)


def test_window_that_is_no_window():
    """A window that reaches the whole sequence traces the causal program;
    one without `causal`, or of no key, is refused."""
    q, k, v = _rand_qkv(jax.random.key(1), 1, 256, 2, 2, 32)
    tiles = dict(block_q=128, block_k=128, sub=64)
    text = lambda **kw: jax.jit(lambda q, k, v: flash_attention(
        q, k, v, **tiles, **kw)).lower(q, k, v).as_text()
    assert text(window=256) == text() != text(window=255)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, window=0)
