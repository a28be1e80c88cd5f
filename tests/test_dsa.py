"""The learned-sparse-attention mixer ("dsa", models/transformer.py
`_dsa_mixer` over ops/sparse_attention.py) at a small size on the CPU, the
kernels interpreted and every call jitted: the exact top-k with its tie rule,
the program against the plain reference (chipbench/reference/keye_vl2.py) on
seeded weights (loss, every gradient group, the selection), what the layer is
where nothing can be left out, M-RoPE, where the indexer's gradient comes
from, and the positions a batch carries."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, transformer as tfm
from ray_tpu.ops import sparse_attention as sa
from test_kda_remat import _kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import weights_keye_vl2 as W  # noqa: E402
from chipbench.reference import keye_vl2 as ref  # noqa: E402

pytestmark = pytest.mark.usefixtures("exact_matmuls")

S = 64  # the preset's length: two key planes of a word


def _sizes(cfg):
    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.KeyeSizes(tc, cfg.norm_eps)


def _batch(cfg, seed=5, B=2):
    """Tokens, three position streams that differ over an "image" of 4 x 4
    patches (Qwen2-VL's rule) and a mask that skips it."""
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (B, S + 1), dtype=np.int32)
    t = np.arange(S + 1)
    pos = np.stack([t, t, t])
    at, side = 20, 4
    img = np.arange(side * side)
    pos[0, at:at + 16] = at
    pos[1, at:at + 16] = at + img // side
    pos[2, at:at + 16] = at + img % side
    pos[:, at + 16:] = at + side + np.arange(S + 1 - at - 16)
    mask = np.ones((B, S + 1), np.int32)
    mask[:, at:at + 16] = 0
    return {"tokens": jnp.asarray(toks),
            "positions": jnp.asarray(np.broadcast_to(
                pos[:, None], (3, B, S + 1)).astype(np.int32)),
            "mask": jnp.asarray(mask)}


@pytest.fixture(scope="module")
def case():
    with jax.default_matmul_precision("highest"):
        cfg = configs.keye_vl2_tiny(dtype=jnp.float32)
        sz = _sizes(cfg)
        key = jax.random.key(54)
        params = W.program_params(key, sz, cfg)
        batch = _batch(cfg)

        def prog(p):
            return tfm.loss_fn(p, batch, cfg, shift_inputs=True,
                               with_counters=True, with_selection=True)

        (loss, counters), g = jax.jit(jax.value_and_grad(
            prog, has_aux=True))(params)
        own = jax.jit(lambda: ref.loss_and_grads(key, batch, sz))()
        given = jax.jit(lambda s: ref.loss_and_grads(
            key, batch, sz, selection=s))(counters["dsa_selection"])
    return dict(cfg=cfg, sz=sz, key=key, params=params, batch=batch,
                loss=loss, counters=counters, grads=g, own=own, given=given)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("topk", [1, 5, 24, 64])
def test_the_selection_is_the_exact_top_k_ties_to_the_lower_index(topk):
    """Scores rounded to a few values, so that most rows tie at their
    threshold: the bits are the first `topk` of a stable descending sort of
    the causal scores, a row's count is min(t + 1, topk), and the rows'
    logsumexp is over the kept set."""
    B, HI, dI = 2, 4, 8
    ks = jax.random.split(jax.random.key(topk), 3)
    qi = jnp.round(jax.random.normal(ks[0], (B, S, HI, dI)))
    ki = jnp.round(jax.random.normal(ks[1], (B, S, dI)))
    w = jnp.round(jax.random.normal(ks[2], (B, S, HI)) * 2) / 8
    bits, lse, count, passes, way = jax.jit(
        lambda a, b, c: sa.select(a, b, c, topk))(
        jnp.swapaxes(qi, 1, 2), jnp.swapaxes(ki, 1, 2),
        jnp.swapaxes(w, 1, 2)[:, :, None])
    got = np.asarray(sa.mask_of(bits))
    score = np.einsum("btj,btjs->bts", w, np.maximum(
        np.einsum("btjd,bsd->btjs", qi, ki), 0))
    tied = 0
    for b in range(B):
        for t in range(S):
            sc = np.where(np.arange(S) <= t, score[b, t], -np.inf)
            keep = np.lexsort((np.arange(S), -sc))[:min(t + 1, topk)]
            want = np.zeros(S, bool)
            want[keep] = True
            assert (got[b, t] == want).all(), (b, t)
            tied += (sc[keep].min() == sc[~want]).any()
            np.testing.assert_allclose(
                lse[b, 0, 0, t], np.log(np.exp(score[b, t][want]).sum()),
                rtol=1e-5)
    assert tied > S // 2 or topk == 64  # the tie rule was exercised
    assert (np.asarray(count)[:, 0, 0]
            == np.minimum(np.arange(S) + 1, topk)).all()
    # one block of rows a sequence: the tie search ran (its six passes after
    # the value's at most 32), or every row kept all its keys and none did
    nbits, passes = (S - 1).bit_length(), np.asarray(passes) / sa._SAMPLE
    assert way.tolist() == [[1 if topk < S else 0]] * B
    assert ((passes > nbits) & (passes <= 32 + nbits)).all() or topk == S
    assert (passes == 0).all() or topk < S


def _scores_by_hand(kind, S, rng):
    """w [S], q [S], k [S] of a one-head indexer one wide, whose score
    I[t, s] = w[t] relu(q[t] k[s]) numpy forms bit for bit (one product, one
    rounding each), and the `topk` of the case."""
    one = np.ones(S, np.float32)
    if kind in ("continuous", "all_kept"):
        # distinct positive scores, every column like every other
        return (one, rng.uniform(0.5, 1.5, S).astype(np.float32),
                rng.uniform(1.0, 2.0, S).astype(np.float32),
                256 if kind == "continuous" else S)
    if kind == "misled":
        # columns 512..1023 hold every late row's largest scores, and the
        # sample (128 lanes of every second chunk of 512) holds none of them
        k = rng.uniform(1.0, 2.0, S).astype(np.float32)
        k[512:1024] += 8.0
        return one, rng.uniform(0.5, 1.5, S).astype(np.float32), k, 256
    if kind == "met_twice":  # every key a second time, next to the first
        k = np.repeat(rng.uniform(1.0, 2.0, S // 2).astype(np.float32), 2)
        return one, rng.uniform(0.5, 1.5, S).astype(np.float32), k, 25
    if kind == "equal":  # a row's scores all the same
        return rng.uniform(0.5, 1.5, S).astype(np.float32), one, one, 24
    if kind == "negative":  # every score below zero, all distinct
        return (-one, rng.uniform(0.5, 1.5, S).astype(np.float32),
                rng.uniform(1.0, 2.0, S).astype(np.float32), 24)
    assert kind == "zeros"  # +0.0 and -0.0 among positive and negative
    return (np.where(rng.random(S) < 0.5, -one, one),
            rng.uniform(0.5, 1.5, S).astype(np.float32),
            rng.normal(size=S).astype(np.float32), 24)


@pytest.mark.parametrize("kind,S_,ways", [
    ("continuous", 2048, {0}), ("misled", 2048, {0, 2}), ("equal", 512, {1}),
    ("met_twice", 512, {1}),
    ("negative", 512, {0}), ("zeros", 512, {1}), ("all_kept", 512, {0})])
def test_the_search_goes_each_way_and_keeps_the_exact_top_k(kind, S_, ways):
    """`select`'s adaptive search on scores made by hand: the bits are the
    first `topk` of a stable descending sort whichever way a block of rows
    went (0: the sample's bracket held, or none was taken, and no tie was
    left out; 1: the tie search ran; 2: a count over the whole block refused
    the sample's bracket), and the passes say so."""
    w, q, k, topk = _scores_by_hand(kind, S_, np.random.default_rng(55))
    bits, lse, count, passes, way = jax.jit(
        lambda a, b, c: sa.select(a, b, c, topk))(
        jnp.asarray(q)[None, None, :, None], jnp.asarray(k)[None, None],
        jnp.asarray(w)[None, None, None])
    score = w[:, None] * np.maximum(q[:, None] * k[None, :], np.float32(0))
    score = np.where(np.tri(S_, dtype=bool), score, -np.inf)
    order = np.argsort(-score, axis=1, kind="stable")
    rank = np.empty_like(order)
    np.put_along_axis(rank, order, np.arange(S_)[None, :], axis=1)
    want = rank < np.minimum(np.arange(S_) + 1, topk)[:, None]
    np.testing.assert_array_equal(np.asarray(sa.mask_of(bits))[0], want)
    assert (np.asarray(count)[0, 0, 0] == want.sum(1)).all()
    np.testing.assert_allclose(lse[0, 0, 0], np.log(np.where(
        want, np.exp(score), 0).sum(1)), rtol=1e-5)
    R = sa.plan(S_).rows
    way, passes = np.asarray(way)[0], np.asarray(passes)[0] / sa._SAMPLE
    nbits = (S_ - 1).bit_length()
    assert set(way.tolist()) == ways, way
    # a block whose rows keep every key counts nothing; no block runs more
    # than the value's 32 passes, the bracket's and, on way 1, the index's
    first = np.arange(S_ // R) * R
    assert (passes[first + R <= topk] == 0).all()
    assert (passes <= 32 + 4 + nbits * (way == 1)).all(), passes
    if kind == "continuous":
        # every block stops at an exact count, under the parent's 33 + nbits;
        # the last four (four chunks: a sample is taken) from its bracket
        assert (passes < 33).all(), passes
        assert (passes[first >= 1536] % 1 != 0).all(), passes
    if kind == "misled":
        # rows from 1,536 on have a sample that holds none of their 256
        # largest: the bracket's upper end fails its count
        assert (way[first >= 1536] == 2).all() and (
            way[first < 1536] == 0).all(), way
    if kind in ("equal", "met_twice"):  # a tie left out: the index's passes
        assert (passes[first >= topk] > nbits).all()


def test_the_program_is_the_reference(case):
    """Loss, both of its parts, the selection and the counter; then, GIVEN
    the program's selection, every compared gradient leaf."""
    cfg, c = case["cfg"], case["counters"]
    loss_r, _, aux = case["own"]
    assert abs(float(case["loss"]) - float(loss_r)) < 2e-5
    assert abs(float(c["dsa_index_loss"]) - float(aux["index"])) < 2e-5
    assert float(c["dsa_selected"]) == float(aux["kept"]) == (
        cfg.n_layers * 2 * sum(min(t + 1, cfg.dsa_topk) for t in range(S)))
    # float32 against float32: the two selections are the same sets
    np.testing.assert_array_equal(c["dsa_selection"], aux["bits"])
    loss_g, g_r, aux_g = case["given"]
    assert float(aux_g["missed"]) == 0.0 and float(aux_g["margin"]) == 0.0
    assert abs(float(case["loss"]) - float(loss_g)) < 2e-5
    got = W.program_leaves(cfg, case["sz"], case["grads"])
    assert set(got) == set(g_r)
    for leaf, want in g_r.items():
        assert float(jnp.linalg.norm(want)) > 0, leaf
        assert _rel(got[leaf], want) < 3e-5, leaf


def test_the_searchs_counters_reach_the_phase_table(case):
    """`dsa_select_passes` (the counting passes, in eighths, summed over
    layers and blocks of rows) and `dsa_select_fallback` (the blocks off way
    0, summed over layers) are whole-number counters of a "dsa" stack, and
    `observe_counters` folds their VALUES into the phase table."""
    from ray_tpu.train.step import ShardedTrainStep
    from ray_tpu.util import tracing

    c = {n: a for n, a in case["counters"].items() if n != "dsa_selection"}
    blocks = 2 * case["batch"]["tokens"].shape[0]  # two layers' one block each
    # the preset's 64 rows are one block with no sample: a whole number of
    # passes, at most the value's 32 and the index's six
    passes = int(c["dsa_select_passes"])
    assert passes % sa._SAMPLE == 0
    assert 0 < passes <= 38 * sa._SAMPLE * blocks
    assert 0 <= int(c["dsa_select_fallback"]) <= blocks
    names = ("dsa_select_passes", "dsa_select_fallback")
    row = lambda n: tracing.phase_table().get(
        "train." + n, {"count": 0, "total_ns": 0})
    before = {n: row(n) for n in names}
    seen = ShardedTrainStep.observe_counters(c)
    for n in names:
        assert seen[n] == int(c[n])
        assert row(n)["count"] == before[n]["count"] + 1
        assert row(n)["total_ns"] == before[n]["total_ns"] + int(c[n])


def test_a_wrong_selection_is_seen(case):
    """The comparison's own readings move when the selection is not the
    reference's: keys 0..k-1 for every late query miss most of its top-k by
    a margin of the scores' own spread."""
    cfg, sz = case["cfg"], case["sz"]
    t, s = np.arange(S)[:, None], np.arange(S)[None, :]
    first = (s <= t) & (s < cfg.dsa_topk)
    bits = jnp.broadcast_to(ref.pack(jnp.asarray(first)),
                            case["counters"]["dsa_selection"].shape)
    _, _, aux = jax.jit(lambda b: ref.loss_and_grads(
        case["key"], case["batch"], sz, selection=b))(bits)
    assert float(aux["kept"]) == float(case["counters"]["dsa_selected"])
    assert float(aux["missed"]) / float(aux["kept"]) > 0.2
    assert float(aux["margin"]) > 0.5


def test_where_nothing_is_left_out_the_layer_is_the_attn_layer(case):
    """`dsa_topk` >= S keeps every earlier key: the stack's loss less the
    indexer's term is the loss of the same stack with "attn" layers (the
    same leaves without the indexer's)."""
    whole = dataclasses.replace(case["cfg"], dsa_topk=S)
    plain = dataclasses.replace(whole, dsa_layers=())
    strip = lambda seg: [{n: a for n, a in layer.items()
                          if not n.startswith("dsa_")} for layer in seg]
    p_plain = dict(case["params"], layers=[
        strip(seg) for seg in case["params"]["layers"]])
    loss, c = jax.jit(lambda p: tfm.loss_fn(
        p, case["batch"], whole, shift_inputs=True, with_counters=True))(
        case["params"])
    want = jax.jit(lambda p: tfm.loss_fn(
        p, case["batch"], plain, shift_inputs=True))(p_plain)
    assert float(c["dsa_selected"]) == whole.n_layers * 2 * S * (S + 1) / 2
    assert abs(float(loss) - float(c["dsa_index_loss"]) - float(want)) < 2e-5


def test_mrope_with_three_equal_streams_is_rope():
    x = jax.random.normal(jax.random.key(0), (2, S, 4, 16))
    pos = jnp.broadcast_to(jnp.arange(S)[None] * 3 + 1, (2, S))
    plain = jax.jit(lambda x: tfm._rope(x, pos, 1e4))(x)
    three = jax.jit(lambda x: tfm._rope(
        x, jnp.stack([pos] * 3), 1e4, None, (2, 3, 3)))(x)
    np.testing.assert_array_equal(plain, three)
    # and a stream of its own turns its section's pairs alone
    moved = jnp.stack([pos, pos + 7, pos])
    got = jax.jit(lambda x: tfm._rope(x, moved, 1e4, None, (2, 3, 3)))(x)
    same = np.isclose(got, plain, atol=1e-6).all(axis=(0, 1, 2))
    assert same.tolist() == [True] * 2 + [False] * 3 + [True] * 3 + [
        True] * 2 + [False] * 3 + [True] * 3
    with pytest.raises(ValueError, match="rope_sections"):
        tfm._rope(x, moved, 1e4)
    with pytest.raises(ValueError, match="add up"):
        configs.keye_vl2_tiny(rope_sections=(2, 3, 4))


def test_the_indexer_learns_from_its_loss_alone_and_nothing_else_does(case):
    """L = L_LM + DSA_LOSS_COEF x the counter `dsa_index_loss`: each part
    differentiated alone."""
    cfg, params, batch = case["cfg"], case["params"], case["batch"]

    def parts(p):
        loss, c = tfm.loss_fn(p, batch, cfg, shift_inputs=True,
                              with_counters=True)
        index = c["dsa_index_loss"]
        return loss - tfm.DSA_LOSS_COEF * index, index

    g_lm = jax.jit(jax.grad(lambda p: parts(p)[0]))(params)
    g_ix = jax.jit(jax.grad(lambda p: parts(p)[1]))(params)
    flat = lambda g: {jax.tree_util.keystr(k): a for k, a in
                      jax.tree_util.tree_leaves_with_path(g)}
    for name, a in flat(g_lm).items():
        assert (float(jnp.abs(a).max()) == 0.0) == ("dsa_" in name), name
    for name, a in flat(g_ix).items():
        assert (float(jnp.abs(a).max()) > 0.0) == ("dsa_" in name), name


def test_a_batch_without_positions_counts_them_itself(case):
    cfg = case["cfg"]
    toks = case["batch"]["tokens"]
    f = jax.jit(lambda b: tfm.loss_fn(case["params"], b, cfg,
                                      shift_inputs=True))
    arange = jnp.broadcast_to(jnp.arange(S + 1, dtype=jnp.int32),
                              (3,) + toks.shape)
    assert float(f({"tokens": toks})) == float(
        f({"tokens": toks, "positions": arange}))
    assert float(f({"tokens": toks})) != float(
        f({"tokens": toks, "positions": case["batch"]["positions"]}))
    # in place: the positions are not cut
    g = jax.jit(lambda b: tfm.loss_fn(case["params"], b, cfg))
    assert float(g({"tokens": toks[:, :S]})) == float(
        g({"tokens": toks[:, :S], "positions": arange[:, :, :S]}))


def test_the_cores_folded_grid_is_the_rectangular_one_bit_for_bit(
        monkeypatch):
    """`_attend_fwd` / `_attend_bwd` at 256 positions with tiles of 8 x 8: a
    key tile lies inside a plane of S / 32 keys, so a square grid has a
    multiple of 32 tiles a side, here 32 as in the cell (16 x 33 grid steps
    a head, all tiles, for the rectangle's 1,024). Against autodiff of the
    plain masked softmax over the same bits, and against the SAME call on
    the rectangular grid (the rule answering no, here in the test): o, lse,
    dK and dV bit for bit, dQ within float32 rounding (the fused backward
    sums a row's key blocks in the order the grid brings them)."""
    from ray_tpu.ops import flash_attention as fa

    S_, H, KVH, D, HI, dI, topk, scale, tiles = 256, 4, 2, 16, 2, 8, 40, .25, (
        8, 8)
    assert fa._folded(True, *tiles, S_) and fa.grid_steps(
        S_, *tiles, True) == (528, 0)
    ks = jax.random.split(jax.random.key(57), 7)
    q, do = (jax.random.normal(x, (1, H, S_, D)) for x in ks[:2])
    k, v = (jax.random.normal(x, (1, KVH, S_, D)) for x in ks[2:4])
    bits = jax.jit(lambda a, b, c: sa.select(a, b, c, topk))(
        jax.random.normal(ks[4], (1, HI, S_, dI)),
        jax.random.normal(ks[5], (1, dI, S_)),
        jax.random.normal(ks[6], (1, HI, 1, S_)))[0]

    def core(q, k, v, bits, do):
        o, lse = sa._attend_fwd(q, k, v, bits, scale, tiles)
        return (o, lse) + tuple(sa._attend_bwd(q, k, v, bits, o, lse, do,
                                               scale, tiles))

    def plain(q, k, v, bits, do):
        def attend(q, k, v):
            kk, vv = (jnp.repeat(x, H // KVH, axis=1) for x in (k, v))
            s = jnp.where(sa.mask_of(bits)[:, None], jnp.einsum(
                "bhqd,bhkd->bhqk", q, kk) * scale, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), vv)
        o, vjp = jax.vjp(attend, q, k, v)
        return (o,) + vjp(do)

    got = jax.jit(core)(q, k, v, bits, do)
    for module in (fa, sa):
        monkeypatch.setattr(module, "_folded", lambda *a, **kw: False)
    rect = jax.jit(lambda *a: core(*a))(q, k, v, bits, do)
    want = jax.jit(plain)(q, k, v, bits, do)
    want = (want[0], None) + want[1:]
    for name, a, b, c in zip(("o", "lse", "dq", "dk", "dv"), got, rect, want):
        if name == "dq":
            assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * float(
                jnp.max(jnp.abs(b)))
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)
        if c is not None:
            assert float(jnp.max(jnp.abs(a - c))) <= 1e-5 * max(
                float(jnp.max(jnp.abs(c))), 1.0), name


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_keeps_the_selection_and_re_runs_no_kernel(case, policy,
                                                         monkeypatch):
    """Under either policy the gradient is the plain program's and the
    kernels' residuals are kept: the traced gradient has each kernel once a
    layer."""
    cfg = case["cfg"]
    remat = dataclasses.replace(cfg, remat=True, remat_policy=policy)
    grad = lambda cfg: jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, case["batch"], cfg, shift_inputs=True)))
    want_loss, want = grad(cfg)(case["params"])
    loss, got = grad(remat)(case["params"])
    assert abs(float(loss) - float(want_loss)) < 1e-6
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # each kernel once a layer (select 3 in / 5 out, forward 4 / 2, backward
    # 7 / 3, the indexer's loss 8 / 4): the backward's recomputation re-runs
    # none
    from ray_tpu.util import tracing

    plans = []
    observe = tracing.observe
    monkeypatch.setattr(tracing, "observe", lambda name, *a, **kw: (
        plans.append(kw) if name == "dsa.plan" else None,
        observe(name, *a, **kw))[1])
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(lambda p: tfm.loss_fn(
        p, case["batch"], remat, shift_inputs=True)))(case["params"]).jaxpr)
    assert calls == {"3in_5out": 2, "4in_2out": 2, "7in_3out": 2,
                     "8in_4out": 2}, calls
    # a traced layer says its plan, with the core's grid: the preset's one
    # block of 64 rows over 32 key tiles of a word's two planes, every step
    # a tile (the cell's 1,024 x 1,024 at 32,768: 528 and 0, folded)
    assert plans and all(
        (p["bq"], p["bk"], p["grid_steps"], p["grid_steps_idle"])
        == (64, 2, 32, 0) for p in plans), plans



def test_a_mesh_is_refused_with_a_sentence(case):
    """The kernels are under no `shard_map` and `shard_batch` would cut the
    three position streams as rows of the batch: under a sharding context of
    more than one device a "dsa" layer, and a batch's positions, say so."""
    from jax.sharding import Mesh

    from ray_tpu.parallel import sharding as shd

    cfg, params, toks = case["cfg"], case["params"], case["batch"]["tokens"]
    devices = np.array(jax.devices()[:2])
    if devices.size < 2:
        pytest.skip("one device")
    with shd.sharding_ctx(Mesh(devices, ("data",)), shd.DEFAULT_RULES):
        with pytest.raises(NotImplementedError, match="dsa.*R22"):
            jax.eval_shape(lambda p: tfm.loss_fn(
                p, {"tokens": toks}, cfg, shift_inputs=True), params)
        with pytest.raises(NotImplementedError, match="position streams"):
            jax.eval_shape(lambda p: tfm.loss_fn(
                p, case["batch"], cfg, shift_inputs=True), params)
    # a mesh of one device is no mesh
    with shd.sharding_ctx(Mesh(devices[:1], ("data",)), shd.DEFAULT_RULES):
        jax.eval_shape(lambda p: tfm.loss_fn(
            p, case["batch"], cfg, shift_inputs=True), params)


def test_the_control_below_the_indexers_float32_reads_apart(case):
    """The reference with L_I formed in bfloat16 (the scores as it reads
    them, their logsumexp, the target; chipbench/limits_sparse.py
    `bf16_index`) is not the float32 reference: L_I moves by bfloat16's
    rounding and nothing else does; with float32 named it is the reference
    bit for bit."""
    sz, key, batch = case["sz"], case["key"], case["batch"]
    run = lambda dt: jax.jit(lambda: ref.loss(key, batch, sz,
                                              index_dtype=dt))()
    _, low = run(jnp.bfloat16)
    loss, same = run(jnp.float32)
    want = float(case["own"][2]["index"])
    assert float(same["index"]) == want
    assert float(loss) == float(case["own"][0])
    assert abs(float(low["index"]) - want) / want > 1e-3
    np.testing.assert_array_equal(low["bits"], same["bits"])
    assert float(low["lm"]) == float(same["lm"])
