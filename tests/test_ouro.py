"""The looped dense stack (models/transformer.py: `loop_steps` passes over
one set of leaves, two norms round every sublayer, the final norm closing
every pass, a head and an exit gate a pass, the loss an expectation over the
exit distribution less its entropy) on the CPU at the tiny preset: the
program against the plain reference (chipbench/reference/ouro.py: nothing
from ray_tpu, whole logits, the exit distribution as products) on seeded
weights, a shared weight's gradient against the sum over four copies, each
mechanism got wrong one way, the exit distribution, the counters and
observations, the counts and the configuration file. What it shares with
the other families is tests/test_model_table.py (plan, lowering, decoding,
the pipeline) and test_fused_ce.py (the per-token head)."""
import contextlib
import dataclasses
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import ouro_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")

CONFIG = os.path.join(ROOT, "chipbench", "configs", "ouro_2_6b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
LEAVES = ("final_norm", "gate_w", "lm_head_rows", "wo_last", "w_down_last",
          "attn_post_norm_last", "mlp_post_norm_last", "wq_first")
WRONG = ("three_passes_for_four", "no_norm_between_passes", "no_post_norms",
         "gate_before_the_final_norm", "last_mass_without_the_remainder",
         "entropy_sign_turned", "beta_0", "mean_mass_for_a_tokens_own")


def _wrong_backbone(kind, params, tokens, cfg, read=None, positions=None):
    """`tfm._backbone`'s passes written out with one thing got wrong: the
    next pass handed the state before the final norm, or the gate reading
    that state."""
    B, S = tokens.shape
    h = tfm.embed_tokens(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    reads = []
    for _ in range(cfg.loop_steps):
        x, _ = tfm._stack_once(params, h, positions, cfg)
        h = tfm._norm(x, params["final_norm"], None, cfg.norm, cfg.norm_eps)
        ce, gate = read(h)
        if kind == "gate_before_the_final_norm":
            gate = jnp.sum(x.astype(jnp.float32) * params["exit_gate_w"],
                           -1) + params["exit_gate_b"]
        reads.append((ce, gate))
        if kind == "no_norm_between_passes":
            h = x
    return x, {}, jax.tree.map(lambda *a: jnp.stack(a), *reads)


@contextlib.contextmanager
def wrong(kind: str, cfg):
    """-> the configuration to run with one mechanism got wrong: a
    configuration field where the mechanism is one, else `_backbone` or
    `exit_log_probs` patched (no option of the program): the chip run at the
    timed sizes (PERF.md section 6) uses the same."""
    fields = {"three_passes_for_four": dict(loop_steps=cfg.loop_steps - 1),
              "no_post_norms": dict(post_norm=False),
              "entropy_sign_turned": dict(
                  exit_entropy_coef=-cfg.exit_entropy_coef),
              "beta_0": dict(exit_entropy_coef=0.0)}
    log_probs = tfm.exit_log_probs
    if kind in fields:
        yield dataclasses.replace(cfg, **fields[kind])
    elif kind == "last_mass_without_the_remainder":
        # p(T) = lam_T S^(T-1) as the passes before it, the last gate read:
        # the masses sum to less than 1
        def short(g):
            return log_probs(g).at[-1].add(jax.nn.log_sigmoid(
                g[-1].astype(jnp.float32)))
        with mock.patch.object(tfm, "exit_log_probs", short):
            yield cfg
    elif kind == "mean_mass_for_a_tokens_own":
        def mean_mass(g):
            p = jnp.exp(log_probs(g))
            mean = p.mean(axis=tuple(range(1, p.ndim)), keepdims=True)
            return jnp.log(jnp.broadcast_to(mean, p.shape))
        with mock.patch.object(tfm, "exit_log_probs", mean_mass):
            yield cfg
    else:
        with mock.patch.object(tfm, "_backbone", lambda *a, **k:
                               _wrong_backbone(kind, *a, **k)):
            yield cfg


def _sizes(cfg, **changes):
    from chipbench import weights_ouro as W

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return W.OuroSizes(dict(tc, **changes), cfg.norm_eps)


def _program(cfg, sz, params, toks):
    """(loss, compared gradient leaves) of the program."""
    from chipbench import weights_ouro as W

    loss, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    return float(loss), W.program_leaves(cfg, sz, g)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def conf():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def case():
    """The tiny preset in float32, seeded weights, and the program's and
    the reference's loss and gradients."""
    from chipbench import weights_ouro as W
    from chipbench.reference import ouro as ref

    cfg = ouro_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(21)
    toks = jax.random.randint(jax.random.key(22), (2, 49), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        params = W.program_params(key, sz, cfg)
        loss_p, got = _program(cfg, sz, params, toks)
        loss_r, want = jax.jit(lambda k, t: ref.loss_and_grads(k, t, sz))(
            key, toks)
    return dict(cfg=cfg, sz=sz, key=key, params=params, toks=toks,
                loss=(loss_p, float(loss_r)), grads=(got, want))


def test_loss_matches_the_reference_and_the_layout_is_inits(case):
    assert abs(case["loss"][0] - case["loss"][1]) < 1e-5
    shapes = jax.eval_shape(lambda: tfm.init_params(jax.random.key(0),
                                                    case["cfg"]))
    assert jax.tree.map(lambda a: a.shape, shapes) == jax.tree.map(
        lambda a: a.shape, case["params"])
    layer = tfm.layer_params(case["params"], case["cfg"], 1)
    assert layer["attn_post_norm"].shape == layer["mlp_post_norm"].shape == (
        64,)
    assert case["params"]["exit_gate_w"].shape == (64,)
    assert case["params"]["exit_gate_b"].shape == (1,)


@pytest.fixture(scope="module")
def chunked(case):
    """(loss, compared leaves) under the cell's own settings: the chunked
    per-token head and remat "full"."""
    cfg = dataclasses.replace(case["cfg"], fused_ce=True, remat=True,
                              remat_policy="full")
    return _program(cfg, case["sz"], case["params"], case["toks"])


@pytest.mark.parametrize("head", ["plain", "chunked"])
@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(case, chunked, leaf, head):
    """Every compared leaf, under the plain head recomputed a pass and under
    the chunked per-token one (the cell's)."""
    got, want = case["grads"]
    if head == "chunked":
        got = chunked[1]
    assert got[leaf].shape == want[leaf].shape
    assert float(jnp.linalg.norm(want[leaf])) > 0
    assert _rel(got[leaf], want[leaf]) < 2e-5, leaf


def test_the_chunked_head_under_remat_gives_the_same_loss(case, chunked):
    assert abs(chunked[0] - case["loss"][1]) < 1e-5


def test_a_shared_weights_gradient_is_the_sum_over_its_four_uses(case):
    """Four copies of the layers, pass t run on copy t (`_stack_once` handed
    them in turn): the shared leaves' gradient is the sum of the copies'."""
    cfg, params = case["cfg"], case["params"]
    batch = {"tokens": case["toks"]}
    shared = jax.jit(jax.grad(lambda p: tfm.loss_fn(
        p, batch, cfg, shift_inputs=True)))(params)
    once = tfm._stack_once

    def loss(copies):
        turn = iter(copies)
        with mock.patch.object(tfm, "_stack_once", lambda p, *a: once(
                dict(p, layers=next(turn)), *a)):
            return tfm.loss_fn(params, batch, cfg, shift_inputs=True)

    each = jax.jit(jax.grad(loss))([params["layers"]] * cfg.loop_steps)
    total = jax.tree.map(lambda *a: sum(a), *each)
    for n, g in shared["layers"].items():
        assert _rel(total[n], g) < 1e-5, n
        assert min(float(jnp.linalg.norm(e[n])) for e in each) > 0, n


def test_two_passes_are_the_stack_applied_twice(case):
    """No gate: `forward` of a stack looped twice is the one-pass stack, the
    final norm, the stack again and the head; and one pass with no post-norm
    and no gate is the plain configuration itself."""
    cfg = dataclasses.replace(case["cfg"], loop_steps=2, exit_gate=False,
                              exit_entropy_coef=0.0)
    params, toks = case["params"], case["toks"][:, :-1]
    once = dataclasses.replace(cfg, loop_steps=1)
    pos = jnp.broadcast_to(jnp.arange(48, dtype=jnp.int32)[None], (2, 48))
    x, _ = tfm._stack_once(params, tfm.embed_tokens(params, toks, once), pos,
                           once)
    h = tfm._norm(x, params["final_norm"], None, "rmsnorm", cfg.norm_eps)
    x, _ = tfm._stack_once(params, h, pos, once)
    np.testing.assert_allclose(tfm.forward(params, toks, cfg),
                               tfm.lm_head(params, x, once), atol=2e-5)
    plain = ouro_tiny(loop_steps=1, post_norm=False, exit_gate=False,
                      exit_entropy_coef=0.0)
    fields = {f.name for f in dataclasses.fields(plain)
              if getattr(plain, f.name) != f.default}
    assert not fields & {"loop_steps", "post_norm", "exit_gate",
                         "exit_entropy_coef"}
    assert "exit_gate_w" not in tfm.param_logical_specs(plain)
    with pytest.raises(ValueError, match="loop_steps"):
        ouro_tiny(loop_steps=1)  # a gate with one pass to choose from
    with pytest.raises(ValueError, match="post_norm"):
        ouro_tiny(norm="layernorm")


def test_the_exit_masses_sum_to_one_and_the_entropys_gradient():
    """`exit_log_probs`: T masses from T - 1 gates, summing to 1 whatever
    the gates; its plain form (products of sigmoids); and the entropy's
    gradient by central differences."""
    g = 2.0 * jax.random.normal(jax.random.key(3), (4, 5))
    logp = tfm.exit_log_probs(g)
    p = jnp.exp(logp)
    assert p.shape == (4, 5)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(g[:3])
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), atol=1e-6)
    np.testing.assert_allclose(p[3], jnp.prod(1 - lam, 0), atol=1e-6)
    np.testing.assert_array_equal(  # the last pass's gate is not read
        logp, tfm.exit_log_probs(g.at[3].set(7.0)))

    def entropy(g):
        lp = tfm.exit_log_probs(g)
        return -(jnp.exp(lp) * lp).sum()

    grad, eps = jax.grad(entropy)(g), 1e-2
    for i, j in ((0, 0), (1, 3), (2, 4)):
        d = jnp.zeros_like(g).at[i, j].set(eps)
        fd = (entropy(g + d) - entropy(g - d)) / (2 * eps)
        assert abs(float(fd) - float(grad[i, j])) < 2e-3, (i, j)
    # saturated gates: no NaN where a mass underflows
    hard = jnp.asarray([[40.0], [-40.0], [0.0], [0.0]])
    assert np.isfinite(np.asarray(jax.grad(entropy)(hard))).all()


@pytest.mark.parametrize("kind", WRONG)
def test_a_mechanism_got_wrong_fails_the_limit(case, conf, kind):
    """Each mechanism made wrong puts the error over the cell's limit (the
    sound program reads 2e-6 here and a few percent in bfloat16 on the
    chip): three passes for four; the next pass handed the state before the
    final norm; no post-norms; the gate reading the state before the final
    norm; the last mass without the remainder; the entropy's sign turned;
    no entropy term; the passes' cross-entropies weighted by the mean mass
    of a pass, not the token's own."""
    with wrong(kind, case["cfg"]) as cfg, \
            jax.default_matmul_precision("highest"):
        _, got = _program(cfg, case["sz"], case["params"], case["toks"])
    want = case["grads"][1]
    worst = max(_rel(got[n], want[n]) for n in LEAVES)
    assert worst > conf["limits"]["train_grad_rel_err"], worst


def test_the_step_returns_the_exit_counters_and_the_loop_says_what_it_is(case):
    """`loss_fn(with_counters=True)`: four mean exit masses, in parts per
    thousand, summing to 1,000, and the mean entropy; `observe_counters`
    folds them as `train.exit_*`; one `train.loop` observation a traced
    backbone with what the loop is; every pass's head under `loop.head`."""
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.train.step import transformer_train_step
    from ray_tpu.util import tracing

    cfg = dataclasses.replace(case["cfg"], fused_ce=True, remat=True,
                              remat_policy="full")
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    ts = transformer_train_step(cfg, mesh, shift_inputs=True,
                                with_counters=True)
    params, opt = ts.init(jax.random.key(0))
    count = lambda n: tracing.phase_table().get(n, {"count": 0})["count"]
    before = {n: count(n) for n in ("train.loop", "train.exit_mass_pm_1")}
    with mock.patch.object(tracing, "observe", wraps=tracing.observe) as spy:
        losses = []
        for _ in range(2):
            params, opt, loss, aux = ts.step(
                params, opt, ts.shard_batch({"tokens": np.asarray(
                    case["toks"])}))
            losses.append(float(loss))
            seen = ts.observe_counters(aux)
    assert losses[1] < losses[0]
    masses = [seen[f"exit_mass_pm_{t}"] for t in (1, 2, 3, 4)]
    assert abs(sum(masses) - 1000.0) < 0.01 and min(masses) > 0
    assert 0 < seen["exit_entropy_pm"] < 1000 * np.log(4) + 1
    assert count("train.exit_mass_pm_1") == before["train.exit_mass_pm_1"] + 2
    assert "train.exit_entropy_pm" in tracing.phase_table()
    loops = [c.kwargs for c in spy.call_args_list if c.args[0] == "train.loop"]
    assert loops == [dict(slow=False, steps=4, layers=2, post_norm=True,
                          gate=True, head="chunked")]
    assert count("train.loop") == before["train.loop"] + 1
    text = ts.lower_step(params, opt, ts.shard_batch(
        {"tokens": np.asarray(case["toks"])})).as_text(debug_info=True)
    assert "loop.head" in text and "transpose(jvp(loop.head))" in text


def test_the_counts_and_the_configuration_file(conf):
    """The file holds every key of the catalog's `config` (the depth
    reduced, nothing else), runs at the published widths, and its counts are
    the issue's: 612,438,017 parameters, 15.5 GFLOP a token at 8,192, the
    benchmark's own count the program's within the norms' and the gate's
    few parameters."""
    from chipbench import weights_ouro as W
    from chipbench.reduce import ouro_counts

    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")
    assert conf["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items() if conf.get(k) != v]
    assert differ == conf["reduced"] == ["num_hidden_layers"]
    assert (conf["num_hidden_layers"], conf["total_ut_steps"]) == (8, 4)
    tc = dict(conf["transformer_config"])
    assert (tc["n_layers"], tc["loop_steps"], tc["vocab_size"], tc["d_model"],
            tc["n_heads"], tc["n_kv_heads"], tc["attn_head_dim"], tc["d_ff"],
            tc["rope_theta"], tc["norm_eps"]) == (
        conf["num_hidden_layers"], conf["total_ut_steps"], conf["vocab_size"],
        conf["hidden_size"], conf["num_attention_heads"],
        conf["num_key_value_heads"], conf["head_dim"],
        conf["intermediate_size"], conf["rope_theta"], conf["rms_norm_eps"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert cfg.num_params() == 8 * 51388416 + 201330689 == 612438017
    deep = dataclasses.replace(cfg, n_layers=48)
    assert deep.num_params() == 2667974657
    assert cfg.flops_per_token(8192) == 15503818776.0
    sz = W.sizes_of(conf, False)
    ours = ouro_counts.stack_flops_per_token(sz, 8192)
    assert 0 <= cfg.flops_per_token(8192) - ours < 2e-4 * ours
    assert ouro_counts.head_flops_per_token(sz) == 6.0 * 4 * 49152 * 2048
    # a stack looped with no gate pays one head
    no_gate = dataclasses.replace(cfg, exit_gate=False, exit_entropy_coef=0.0)
    assert (cfg.flops_per_token(8192) - no_gate.flops_per_token(8192)
            == 6.0 * 3 * 49152 * 2048 + 6.0 * 4 * 2049)
