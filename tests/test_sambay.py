"""The decoder-hybrid-decoder stack (Phi-4-mini-flash, `phi4_flash_tiny`):
Mamba-1 layers, differential attention under a window and in full, a Gated
Memory Unit and a cross layer that read what two earlier layers hand on,
held to chipbench/reference/phi4_flash.py on seeded weights in float32:
logits, loss and EVERY gradient leaf, without remat and under `full`; the
handed-on tensors' cotangents cut, which must then miss; the chunked scan
against the token-by-token one at a length the chunk does not divide;
differential attention through the flash kernels at the window's edge; the
vocabulary slice; what decode and a mesh say. What the families share
(digests, plans, counts, scopes) is in tests/test_model_table.py."""
import dataclasses
import json
import os
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights_phi4_flash as W
from chipbench.reference import phi4_flash as ref
from chipbench.weights import layer_key
from ray_tpu.models import configs, transformer as tfm
from ray_tpu.ops import selective_scan as ss

pytestmark = pytest.mark.usefixtures("exact_matmuls")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = 1e-4   # float32 against float32: rounding reads 1e-6 to 2e-5
S = 40         # five chunks of 8, five windows of 8


def _sizes(cfg):
    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    tc.update(mamba1_inner=cfg.mamba1_channels, mamba1_dt_rank=cfg.mamba1_rank)
    return W.StackSizes(tc, cfg.norm_eps)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def _as_program(top, layers, sz):
    """(`W.top`'s dict, `W.layer`'s dicts) as the program's tree. `to_program`
    only relabels, so it lays a gradient out as it lays the weights out."""
    return dict(top, layers=[[jax.tree.map(lambda a: a[None], W.to_program(
        w, sz, kind))] for w, kind in zip(layers, sz.kinds)])


@pytest.fixture(scope="module")
def case():
    """cfg, sizes, tokens, the seeded weights as the program holds them, and
    the reference's logits, loss and gradient of EVERY leaf (in the program's
    layout)."""
    cfg = configs.phi4_flash_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(3)
    toks = jax.random.randint(jax.random.key(1), (2, S + 1), 0, sz.V)

    @jax.jit
    def make(key):
        given = (W.top(key, sz), [W.layer(layer_key(key, l), sz, kind)
                                  for l, kind in enumerate(sz.kinds)])
        loss, g = jax.value_and_grad(
            lambda g: ref.loss(key, toks, sz, given=g))(given)
        return (_as_program(*given, sz), loss, _as_program(*g, sz),
                ref.forward(key, toks[:, :-1], sz, given=given))

    with jax.default_matmul_precision("highest"):
        params, loss, grads, logits = make(key)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: tfm.init_params(key, cfg)))
    return cfg, sz, toks, params, float(loss), grads, logits


def _loss_and_grads(cfg, toks, params):
    return jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)


def _errors(got, want):
    return {jax.tree_util.keystr(path): _rel(a, b) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))}


@pytest.mark.parametrize("remat", ["off", "full"])
def test_the_stack_is_the_reference(case, remat):
    cfg, sz, toks, params, want_loss, want, want_logits = case
    cfg = dataclasses.replace(cfg, remat=remat != "off", remat_policy="full")
    loss, got = _loss_and_grads(cfg, toks, params)
    assert abs(float(loss) - want_loss) < 1e-5 * want_loss
    errs = _errors(got, want)
    assert len(errs) == 6 * 6 + 3 + 9 * 2 + 8 * 2 + 6 + 2  # every leaf
    assert max(errs.values()) < LIMIT, max(errs.items(), key=lambda e: e[1])
    if remat == "off":
        logits = jax.jit(lambda p: tfm.forward(p, toks[:, :-1], cfg))(params)
        assert _rel(logits, want_logits) < LIMIT


def test_cut_cotangents_of_what_is_handed_on_miss(case):
    """The control: `m`, `k` and `v` reach their readers with their
    cotangents cut. The loss is the same; the emitting Mamba-1 layer and the
    full layer then learn through their own layer alone, and their
    gradients miss the limit by orders of magnitude."""
    cfg, sz, toks, params, want_loss, want, _ = case
    cut = {n: dataclasses.replace(tfm.MIXERS[n], apply=(
        lambda *a, handed, _f=tfm.MIXERS[n].apply, **k: _f(
            *a, handed=jax.lax.stop_gradient(handed), **k)))
        for n in ("gmu", "xattn")}
    with mock.patch.dict(tfm.MIXERS, cut):
        loss, got = _loss_and_grads(cfg, toks, params)
    assert abs(float(loss) - want_loss) < 1e-5 * want_loss
    err = lambda l, n: _rel(got["layers"][l][0][n], want["layers"][l][0][n])
    assert err(sz.l_emit, "mamba1_win") > 0.05
    assert err(sz.l_emit, "mamba1_A_log") > 0.05
    assert err(sz.l_full, "wkv") > 0.05 and err(sz.l_full, "wq") < LIMIT
    # what lies after the readers is untouched
    assert err(sz.l_cross, "wq") < LIMIT and err(sz.l_gmu, "gmu_w2") < LIMIT


def _scan_inputs(Bz, T, Di, N):
    ks = jax.random.split(jax.random.key(0), 6)
    return (jax.random.normal(ks[0], (Bz, T, Di)),
            jax.nn.softplus(jax.random.normal(ks[1], (Bz, T, Di)) - 3.0),
            -jnp.exp(jax.random.uniform(ks[2], (Di, N)) * 2.7),
            jax.random.normal(ks[3], (Bz, T, N)),
            jax.random.normal(ks[4], (Bz, T, N)),
            jax.random.normal(ks[5], (Di,)))


@pytest.mark.parametrize("body", ["xla", "pallas"])
def test_the_chunked_scan_is_the_recurrence(body):
    """Forward and every gradient, 40 tokens in chunks of 16 (two whole
    chunks and half of one); the kernels interpreted at one block of 1,024
    channels."""
    args = _scan_inputs(2, 40, 24 if body == "xla" else ss.BLOCK, 4)
    fn = {"xla": ss.selective_scan_xla, "pallas": ss.selective_scan_pallas}[
        body]

    def both(f):
        w = jnp.cos(jnp.arange(args[0].size) * 0.37).reshape(args[0].shape)
        return jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *a: jnp.sum(f(*a) * w), argnums=tuple(range(6)))(*a)))(
                *args)

    y, g = both(lambda *a: fn(*a, chunk=16))
    y0, g0 = both(ss.selective_scan_recurrent)
    assert _rel(y, y0) < 1e-5
    assert max(_rel(a, b) for a, b in zip(g, g0)) < 1e-5
    plan = ss.chunk_plan(40, 24, 4, 16, False)
    assert (plan["chunks"], plan["kept_state_bytes"],
            plan["all_state_bytes"]) == (3, 4 * 3 * 24 * 4, 4 * 40 * 24 * 4)


def test_differential_attention_through_flash_at_the_windows_edge(
        flash_kernels):
    """Window 512 through the flash kernels (interpreted) against the
    reference's dense maps, and the edge itself: query t sees key t - 511
    and does not see key t - 512."""
    cfg = configs.phi4_flash_tiny(dtype=jnp.float32, sliding_window=512,
                                  max_seq_len=640)
    sz, T, t = _sizes(cfg), 640, 600
    w = W.layer(jax.random.key(5), sz, ("swa", "dense"))
    layer = dict(W.to_program(w, sz, ("swa", "dense")), _index=jnp.int32(1))
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (1, T, sz.H, sz.hd))
    k, v = (jax.random.normal(kk, (1, T, sz.KVH, sz.hd)) for kk in ks[1:])
    run = jax.jit(lambda k, v: tfm._diff_attend(cfg, layer, q, k, v, 512))
    o = run(k, v)
    want = ref._differential(q, k, v, dict(w, wo=jnp.eye(sz.H * sz.hd),
                                           bo=0.0), sz, sz.lam0(1), 512,
                             ref.mm_f32)
    assert _rel(o, want) < LIMIT
    moved = lambda at: float(jnp.abs(
        run(k.at[0, at].add(3.0), v.at[0, at].add(3.0))[0, t] - o[0, t]).max())
    assert moved(t - 511) > 1e-3 and moved(t - 512) == 0.0


def test_ids_come_from_the_vocabulary_slice():
    """The cell's ids are drawn from the slice it holds, and the reference
    refuses an id outside it."""
    from chipbench import traffic_gen

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "phi4_mini_flash_reasoning.json")) as f:
        config = json.load(f)
    sz = W.sizes_of(config, False)
    assert (sz.V, config["published"]["vocab_size"]) == (25008, 200064)
    assert sz.V * 8 == 200064
    pool = traffic_gen.train_tokens({"pool": 2, "batch": 1, "seq": 64}, 7, sz.V)
    assert 0 <= pool.min() and pool.max() < sz.V
    tiny = _sizes(configs.phi4_flash_tiny())
    with pytest.raises(ValueError, match="outside the vocabulary slice"):
        ref.forward(jax.random.key(0), np.full((1, 8), tiny.V), tiny)


def test_decode_and_a_mesh_refuse_with_a_sentence():
    from ray_tpu.models.generate import prefill
    from ray_tpu.parallel import MeshSpec, make_mesh, sharding as shd
    from ray_tpu.parallel.pipeline import pipeline_loss_fn

    cfg = configs.phi4_flash_tiny()
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0), cfg))
    toks = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match=r"Mamba-1 \(mamba1\) layer"):
        prefill(params, toks, cfg, 16)
    for row, word in (("gmu", "memory an earlier layer made"),
                      ("xattn", "one slab that several layers read")):
        assert word in tfm.MIXERS[row].no_decode
    with pytest.raises(NotImplementedError, match="diff_attn"):
        prefill(params, toks, configs.llama_tiny(diff_attn=True), 16)
    mesh = make_mesh(MeshSpec(fsdp=2), devices=jax.devices()[:2])
    with shd.sharding_ctx(mesh, shd.DEFAULT_RULES):
        with pytest.raises(NotImplementedError,
                           match="selective scan run on one chip, not under "
                                 r"a mesh of 2 \(ROADMAP R22 \(h\)"):
            jax.eval_shape(lambda p: tfm.loss_fn(
                p, {"tokens": toks}, cfg), params)
    with pytest.raises(NotImplementedError, match="one segment of layers"):
        pipeline_loss_fn(cfg, make_mesh(MeshSpec(pipe=2),
                                        devices=jax.devices()[:2]))
    with pytest.raises(ValueError, match="reads 'm', which no layer before"):
        configs.phi4_flash_tiny(mamba1_layers=(), swa_layers=(1, 2, 3))
