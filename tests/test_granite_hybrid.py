"""The Mamba-2 / NoPE-attention stack (models/transformer.py `mamba2` mixer,
the four multipliers, `positional="none"` with `attn` layers) on the CPU at
the tiny preset: the program against the plain reference
(chipbench/reference/granite_hybrid.py: nothing from ray_tpu, the recurrence
token by token) on seeded weights, the multipliers, the scanned period
against its layers one by one, the counts and the configuration file. Its
plan, lowering and what decoding refuses are tests/test_model_table.py; the
core itself is tests/test_ssd.py."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import granite_hybrid_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

pytestmark = pytest.mark.usefixtures("exact_matmuls")

TC_KEYS = ("vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
           "d_ff", "mamba_layers", "mamba_heads", "mamba_head_dim",
           "mamba_d_state", "mamba_groups", "mamba_conv", "mamba_chunk",
           "embed_scale", "residual_scale", "attn_scale", "logit_scale")


def _sizes(cfg):
    from chipbench import weights_granite_hybrid as W

    return W.StackSizes({k: getattr(cfg, k) for k in TC_KEYS}, cfg.norm_eps)


@pytest.fixture(scope="module")
def case():
    """The tiny preset in float32, seeded weights in both layouts' terms,
    and the program's and the reference's logits, loss and gradients."""
    from chipbench import weights_granite_hybrid as W
    from chipbench.reference import granite_hybrid as ref

    cfg = granite_hybrid_tiny(dtype=jnp.float32)
    sz, key = _sizes(cfg), jax.random.key(11)
    params = W.program_params(key, sz, cfg)
    toks = jax.random.randint(jax.random.key(12), (2, 41), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        loss_p, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
            p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
        loss_r, g_r = jax.jit(lambda k, t: ref.loss_and_grads(k, t, sz))(
            key, toks)
        logits_p = jax.jit(lambda p, t: tfm.forward(p, t, cfg))(
            params, toks[:, :-1])
        logits_r = jax.jit(lambda k, t: ref.forward(k, t, sz))(
            key, toks[:, :-1])
    return dict(cfg=cfg, sz=sz, key=key, params=params, toks=toks,
                loss=(float(loss_p), float(loss_r)),
                logits=(logits_p, logits_r),
                grads=(W.program_leaves(cfg, sz, g), g_r))


def test_logits_and_loss_match_the_reference(case):
    got, want = case["logits"]
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert abs(case["loss"][0] - case["loss"][1]) < 1e-5


@pytest.mark.parametrize("leaf", ["final_norm", "out_proj", "dt_bias",
                                  "A_log", "conv_w", "attn_wo"])
def test_gradient_leaf_matches_the_reference(case, leaf):
    """The leaves the chip's check compares: the final norm, the last
    Mamba-2 layer's out_proj, the first one's dt_bias, A_log and
    convolution weight (x, B and C channels), the attention layer's wo."""
    got, want = case["grads"][0][leaf], case["grads"][1][leaf]
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-4


@pytest.mark.parametrize("name,default,other", [
    ("embed_scale", 1.0, 12.0), ("residual_scale", 1.0, 0.22),
    ("attn_scale", None, 1.0 / 16), ("logit_scale", 1.0, 8.0)])
def test_each_multiplier_changes_the_output_and_its_default_does_not(
        case, name, default, other):
    """From a stack with all four at their defaults: the multiplier alone
    moves the logits; its default traces nothing (the jaxpr of the plain
    configuration is the one without the field's code path: equal to that of
    a configuration that spells the default out)."""
    base = granite_hybrid_tiny(dtype=jnp.float32, n_layers=6, embed_scale=1.0,
                               residual_scale=1.0, attn_scale=None,
                               logit_scale=1.0)
    params = tfm.init_params(jax.random.key(0), base)
    toks = case["toks"][:, :24]
    f = lambda cfg: tfm.forward(params, toks, cfg)
    changed = dataclasses.replace(base, **{name: other})
    assert float(jnp.abs(f(changed) - f(base)).max()) > 1e-3
    if name == "attn_scale":
        default = base.head_dim ** -0.5  # what None stands for
    spelled = dataclasses.replace(base, **{name: default})
    if name != "attn_scale":
        jaxpr = lambda cfg: str(jax.make_jaxpr(lambda: f(cfg))())
        assert jaxpr(spelled) == jaxpr(base)
    np.testing.assert_allclose(f(spelled), f(base), atol=1e-6)


def test_scanned_period_matches_layer_by_layer(case):
    """The 20-layer stack (one segment of ten kinds, two repeats, under the
    remat policy) against the same layers applied one by one."""
    cfg = granite_hybrid_tiny(dtype=jnp.float32, n_layers=20, remat=True,
                              remat_policy="dots")
    assert [(len(p), r) for p, r in cfg.stack_plan()] == [(10, 2)]
    params = tfm.init_params(jax.random.key(2), cfg)
    toks = case["toks"][:, :20]

    def layer_by_layer(p, toks, cfg):
        x = tfm.embed_tokens(p, toks, cfg)
        pos = jnp.broadcast_to(jnp.arange(toks.shape[1], dtype=jnp.int32),
                               toks.shape)
        for l, kind in enumerate(cfg.layer_kinds()):
            x, *_ = tfm._layer_body(cfg, kind, x, tfm.layer_params(p, cfg, l),
                                   pos)
        return tfm.lm_head(p, x, cfg)

    def logits_and_grads(forward):
        def loss(p):
            logits = forward(p, toks, cfg)
            return jnp.mean(jnp.sin(8 * logits)), logits
        (_, logits), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        return logits, g

    logits, g_scan = logits_and_grads(tfm.forward)
    want_logits, g_loop = logits_and_grads(layer_by_layer)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)
    for l in (0, 5, 9, 10, 15, 19):
        got = tfm.layer_params(g_scan, cfg, l)
        want = tfm.layer_params(g_loop, cfg, l)
        assert ("wo" if l % 10 == 5 else "mamba_wo") in got
        for n in want:
            np.testing.assert_allclose(
                got[n], want[n], err_msg=f"layer {l} {n}",
                atol=1e-6 + 1e-4 * float(jnp.abs(want[n]).max()))


def test_counts_and_the_configuration_file():
    """num_params of the cut is 772,160,448 (ISSUE 31's table) and of the
    whole model 3.19 G; the file keeps every published width; the specs put
    heads on the `heads` axis."""
    with open(os.path.join(
            ROOT, "chipbench", "configs", "granite_4_0_h_micro.json")) as f:
        conf = json.load(f)
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    assert tfm._size(tfm.MIXERS["mamba2"].shapes(cfg)) == 25_847_232
    assert tfm._size(tfm.MIXERS["attn"].shapes(cfg)) == 10_485_760
    assert tfm._size(tfm._ffn_shapes(cfg, "dense")) == 50_331_648
    assert cfg.num_params() == cfg.num_active_params() == 772_160_448
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape))
               for a in jax.tree.leaves(shapes)) == 772_160_448
    whole = dataclasses.replace(cfg, n_layers=40, vocab_size=100352)
    assert round(whole.num_params() / 1e9, 2) == 3.19
    assert [(len(p), r) for p, r in whole.stack_plan()] == [(10, 4)]
    # 6 a matmul parameter a token touches (the tied head once) + attention
    # + nine chunked cores: 4.77 GFLOP a token at 4,096.
    assert round(cfg.flops_per_token(4096) / 1e9, 2) == 4.77
    from chipbench import weights_granite_hybrid as W
    from chipbench.reduce import ssd_counts

    sz = W.sizes_of(conf, False)
    f = ssd_counts.stack_flops_per_token(sz, 4096)
    # The program counts the convolutions', norms' and vectors' parameters
    # as 6 each too (219,168 of 772 M); the benchmark's count does not.
    assert 0 <= cfg.flops_per_token(4096) - f == 6 * (
        9 * (4352 * 5 + 3 * 64 + 4096) + 10 * 2 * 2048 + 2048)
    for key, val in {
            "hidden_size": 2048, "intermediate_size": 8192,
            "shared_intermediate_size": 8192, "num_attention_heads": 32,
            "num_key_value_heads": 8, "mamba_n_heads": 64, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_n_groups": 1, "mamba_d_conv": 4,
            "mamba_expand": 2, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "mamba_chunk_size": 256,
            "embedding_multiplier": 12, "residual_multiplier": 0.22,
            "attention_multiplier": 0.015625, "logits_scaling": 8,
            "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
            "position_embedding_type": "nope", "num_local_experts": 0,
    }.items():
        assert conf[key] == val, key
    assert len(conf["layer_types"]) == 40
    assert [i + 1 for i, t in enumerate(conf["layer_types"])
            if t == "mamba"] == list(cfg.mamba_layers)
    assert conf["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["vocab_size"]) == (10, 12544)
    assert conf["published"]["num_hidden_layers"] == 40
    assert "772,160,448" in conf["deployment"] and conf["assumed"]
    assert (tc["d_model"], tc["d_ff"], tc["n_heads"], tc["n_kv_heads"],
            tc["mamba_heads"], tc["mamba_head_dim"], tc["mamba_d_state"]
            ) == (2048, 8192, 32, 8, 64, 64, 128)
    specs = tfm.param_logical_specs(cfg)["layers"][0][0]
    assert specs["mamba_wzx"] == ("layers", "embed", None, "heads", None)
    assert specs["mamba_wo"] == ("layers", "heads", None, "embed")
    assert specs["mamba_A_log"] == ("layers", "heads")


def test_fused_ce_applies_the_logit_scale(case):
    cfg = dataclasses.replace(case["cfg"], fused_ce=True)
    loss = tfm.loss_fn(case["params"], {"tokens": case["toks"]}, cfg,
                       shift_inputs=True)
    assert abs(float(loss) - case["loss"][0]) < 1e-4
