"""The layer stack (models/transformer.py) where its layers are of several
kinds, on the CPU at small sizes: a plan of several segments scanned against
the same layers applied one by one, the counts, the configuration file, and
the flash kernels at a value width of their own. The shares of an expert
layer are tests/test_expert_shares.py, the train step
tests/test_preset_programs.py, the plans and the two stored formats
tests/test_model_table.py, the KDA core tests/test_kda.py, the program
against the plain reference tests/test_kimi_linear_reference.py."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import kimi_linear_tiny

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def test_counts_match_the_cut():
    """num_params / num_active_params / flops_per_token of the published
    widths at the chip's share, against ISSUE 27's table: KDA 39.5 M, MLA
    29.1 M, dense 63.7 M, expert layer 64.3 M, embedding + head 94.4 M,
    602.4 M in all; 336 M matmul parameters a token touches."""
    with open(os.path.join(
            ROOT, "chipbench", "configs", "kimi_linear_48b_a3b.json")) as f:
        conf = json.load(f)
    tc = dict(conf["transformer_config"])
    tc["dtype"], tc["param_dtype"] = jnp.bfloat16, jnp.float32
    cfg = tfm.TransformerConfig(**tc)
    M = 1e6
    size = lambda shapes: round(tfm._size(shapes) / M, 1)
    assert size(tfm.MIXERS["kda"].shapes(cfg)) == 39.5
    assert size(tfm.MIXERS["mla"].shapes(cfg)) == 29.1
    assert size(tfm._ffn_shapes(cfg, "dense")) == 63.7
    assert size(tfm._ffn_shapes(cfg, "moe")) == 64.3
    assert round(2 * cfg.vocab_size * cfg.d_model / M, 1) == 94.4
    n = cfg.num_params()
    print(f"kimi_linear_48b_a3b at the chip's share: {n:,} parameters, "
          f"{16 * n / 1e9:.2f} GB at 16 B a parameter")
    assert round(n / M, 1) == 602.4
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == n
    touched = cfg.num_active_params() - cfg.vocab_size * cfg.d_model
    assert 335 < touched / M < 337, touched
    assert cfg.layer_kinds() == (("kda", "dense"), ("kda", "moe"),
                                 ("kda", "moe"), ("mla", "moe"),
                                 ("kda", "moe"))
    # 6 a touched parameter, plus attention and the KDA core.
    f = cfg.flops_per_token(8192)
    assert 6 * touched < f < 6 * touched + 0.5e9
    # The published widths are in the file unchanged.
    for key, val in {"hidden_size": 2304, "intermediate_size": 9216,
                     "moe_intermediate_size": 1024, "kv_lora_rank": 512,
                     "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                     "v_head_dim": 128, "num_attention_heads": 32,
                     "num_experts_per_token": 8, "num_shared_experts": 1,
                     "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
                     "first_k_dense_replace": 1, "head_dim": 72}.items():
        assert conf[key] == val, key
    assert conf["linear_attn_config"]["head_dim"] == 128
    assert conf["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert conf["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (5, 8, 20480)
    assert (tc["d_model"], tc["d_ff"], tc["moe_d_ff"], tc["moe_num_experts"],
            tc["moe_held"][1]) == (2304, 9216, 1024, 256, 8)
    assert "32 chips" in conf["deployment"] and conf["assumed"]


def _layer_by_layer(params, toks, cfg):
    """The stack as a Python loop over its layers, each taken out of the
    stored tree by `layer_params`: no plan, no scan, no remat."""
    x = tfm.embed_tokens(params, toks, cfg)
    positions = jnp.broadcast_to(jnp.arange(toks.shape[1], dtype=jnp.int32),
                                 toks.shape)
    for l, kind in enumerate(cfg.layer_kinds()):
        x, *_ = tfm._layer_body(cfg, kind, x, tfm.layer_params(params, cfg, l),
                               positions)
    return tfm.lm_head(params, x, cfg)


@pytest.mark.parametrize("name,over,plan", [
    # kda | mla | attn: one layer of each mixer, the last in neither list
    ("attn_tail", dict(n_layers=3, kda_layers=(1,), mla_layers=(2,)),
     [(1, 1), (1, 1), (1, 1)]),
    # (kda, attn) twice, then kda: a pattern of two positions with repeats,
    # under the remat policy
    ("kda_attn_pairs", dict(n_layers=5, kda_layers=(1, 3, 5), mla_layers=(),
                            moe_first_dense=0, remat=True,
                            remat_policy="dots"),
     [(2, 2), (1, 1)]),
    # grouped-query attention between mla layers, every layer with experts
    ("gqa_among_mla", dict(n_layers=4, kda_layers=(), mla_layers=(1, 2, 4),
                           n_kv_heads=2, moe_first_dense=0),
     [(1, 2), (1, 1), (1, 1)]),
])
def test_scanned_segments_match_layer_by_layer(name, over, plan):
    """A softmax-attention layer among kda / mla ones (rope on it alone), in
    a plan of several segments: `_backbone`'s scans against the same layers
    applied one by one. Logits, and the gradient of every leaf of every
    layer, found through `stack_segments` / `layer_slot` on both sides."""
    cfg = kimi_linear_tiny(dtype=jnp.float32, positional="rope",
                           moe_held=(4, 8), **over)
    assert [(len(p), r) for p, r in cfg.stack_plan()] == plan
    mixers = {m for m, _ in cfg.layer_kinds()}
    assert "attn" in mixers and len(mixers) >= 2
    params = tfm.init_params(jax.random.key(2), cfg)
    toks = jax.random.randint(jax.random.key(3), (2, 24), 0, cfg.vocab_size)

    def logits_and_grads(forward):  # one compile a side
        def loss(p):
            logits = forward(p, toks, cfg)
            return jnp.mean(jnp.sin(logits)), logits
        (_, logits), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        return logits, g

    logits, g_scan = logits_and_grads(tfm.forward)
    want_logits, g_loop = logits_and_grads(_layer_by_layer)
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)
    for l, (mixer, ffn) in enumerate(cfg.layer_kinds()):
        got = tfm.layer_params(g_scan, cfg, l)
        want = tfm.layer_params(g_loop, cfg, l)
        assert ({"attn": "wo", "mla": "mla_wo", "kda": "kda_wo"}[mixer] in got
                and {"dense": "w_down", "moe": "moe_w_down"}[ffn] in got)
        for n in want:
            if n == "router_bias":
                continue  # a buffer
            np.testing.assert_allclose(
                got[n], want[n], err_msg=f"{name} layer {l} {n}",
                atol=1e-6 + 1e-4 * float(jnp.abs(want[n]).max()))


@pytest.mark.parametrize("D,Dv,kvh", [(24, 16, 4), (16, 16, 2), (8, 16, 4)])
def test_flash_value_width_of_its_own(D, Dv, kvh):
    """The flash kernels with values narrower or wider than keys (MLA: 192
    / 128), forward and all three gradients, against dense attention."""
    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(D), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, D))
    k = jax.random.normal(ks[1], (2, 64, kvh, D))
    v = jax.random.normal(ks[2], (2, 64, kvh, Dv))
    f = lambda q, k, v: flash_attention(q, k, v, block_q=32, block_k=32)
    out = jax.jit(f)(q, k, v)  # (jitted programs: ROADMAP D11)
    assert out.shape == (2, 64, 4, Dv)
    np.testing.assert_allclose(out, jax.jit(reference_attention)(q, k, v),
                               atol=2e-5)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    grads = lambda fn: jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(grads(f), grads(reference_attention)):
        np.testing.assert_allclose(a, b, atol=5e-5)
