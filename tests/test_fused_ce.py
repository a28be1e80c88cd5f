"""Chunked fused lm-head + cross-entropy (ops/fused_ce.py): value and
gradients must match the unfused logits->CE pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.fused_ce import _pick_chunk, fused_ce, fused_token_ce


def _reference(x, head, targets, valid):
    logits = (x @ head).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return -(((at - lse) * valid).sum() / jnp.maximum(valid.sum(), 1.0))


@pytest.mark.parametrize("chunk", [0, 16, 64])
def test_value_and_grads_match_reference(chunk):
    rng = np.random.default_rng(0)
    M, d, V = 48, 32, 256
    x = jnp.asarray(rng.standard_normal((M, d)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((d, V)) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.integers(0, V, M), jnp.int32)
    valid = jnp.asarray((rng.random(M) > 0.2).astype(np.float32))

    ref_loss, (ref_dx, ref_dh) = jax.value_and_grad(
        _reference, argnums=(0, 1))(x, head, targets, valid)
    fused_loss, (dx, dh) = jax.value_and_grad(
        fused_ce, argnums=(0, 1))(x, head, targets, valid, chunk)
    np.testing.assert_allclose(fused_loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(dx, ref_dx, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(dh, ref_dh, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("chunk", [0, 16])
def test_per_token_values_and_grads_match_token_cross_entropy(chunk):
    """`fused_token_ce`: every row's cross-entropy is `token_cross_entropy`'s
    of that row alone, and a loss that weighs each row by a weight of its own
    has the unfused pipeline's gradients with respect to x, the head and the
    weights."""
    from ray_tpu.models.transformer import token_cross_entropy

    rng = np.random.default_rng(2)
    M, d, V = 48, 32, 256
    x = jnp.asarray(rng.standard_normal((M, d)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((d, V)) * 0.1, jnp.float32)
    targets = jnp.asarray(rng.integers(0, V, M), jnp.int32)
    w = jnp.asarray(rng.random(M), jnp.float32)

    def per_token(x, head):
        logits = (x @ head).astype(jnp.float32)[:, None]   # [M,1,V]
        one = jnp.ones((1, 1), jnp.float32)
        return jax.vmap(lambda l, t: token_cross_entropy(
            l[None], t[None, None], one))(logits, targets)

    ce = fused_token_ce(x, head, targets, chunk)
    assert ce.shape == (M,) and ce.dtype == jnp.float32
    np.testing.assert_allclose(ce, per_token(x, head), rtol=1e-5, atol=1e-6)
    want = jax.grad(lambda x, h, w: (w * per_token(x, h)).sum(),
                    argnums=(0, 1, 2))(x, head, w)
    got = jax.grad(lambda x, h, w: (w * fused_token_ce(
        x, h, targets, chunk)).sum(), argnums=(0, 1, 2))(x, head, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-6)
    # the mean is the weighted sum of the rows over the weights' sum
    np.testing.assert_allclose(fused_ce(x, head, targets, w, chunk),
                               (w * ce).sum() / w.sum(), rtol=1e-5)


def test_bf16_inputs_accumulate_f32():
    rng = np.random.default_rng(1)
    M, d, V = 32, 16, 128
    x = jnp.asarray(rng.standard_normal((M, d)), jnp.bfloat16)
    head = jnp.asarray(rng.standard_normal((d, V)) * 0.1, jnp.bfloat16)
    targets = jnp.asarray(rng.integers(0, V, M), jnp.int32)
    valid = jnp.ones(M, jnp.float32)
    loss = fused_ce(x, head, targets, valid, 32)
    ref = _reference(x.astype(jnp.float32), head.astype(jnp.float32),
                     targets, valid)
    assert abs(float(loss) - float(ref)) < 0.05  # bf16 matmul tolerance
    dx, dh = jax.grad(fused_ce, argnums=(0, 1))(x, head, targets, valid, 32)
    assert dx.dtype == jnp.bfloat16 and dh.dtype == jnp.bfloat16


def test_pick_chunk():
    assert _pick_chunk(32000) == 3200   # largest 128-multiple divisor
    assert _pick_chunk(4096) == 4096
    assert _pick_chunk(977) == 977      # prime: ONE chunk, never [M,1] scans
    assert _pick_chunk(32003) == 32003  # prime-ish vocab, same
    assert _pick_chunk(4000) == 4000    # largest divisor when no 128-mult


def test_model_loss_path_matches_unfused():
    """cfg.fused_ce=True computes the same training loss (and grads) as
    the default path on a tiny decoder, both token conventions."""
    import dataclasses

    from ray_tpu.models.configs import llama_tiny
    from ray_tpu.models.transformer import init_params, loss_fn

    cfg = llama_tiny()
    params = init_params(jax.random.key(0), cfg)
    rngs = np.random.default_rng(2)
    for shift in (False, True):
        S = cfg.max_seq_len
        tokens = jnp.asarray(
            rngs.integers(0, cfg.vocab_size,
                          (2, S + 1 if shift else S)), jnp.int32)
        batch = {"tokens": tokens}
        fused_cfg = dataclasses.replace(cfg, fused_ce=True)
        # (a jitted program a path: op by op the small ops compile alone)
        both = lambda c: jax.jit(jax.value_and_grad(lambda p: loss_fn(
            p, batch, c, shift_inputs=shift)))(params)
        (base, g_base), (fused, g_fused) = both(cfg), both(fused_cfg)
        np.testing.assert_allclose(float(fused), float(base), rtol=2e-4)

        flat_b = jax.tree.leaves(g_base)
        flat_f = jax.tree.leaves(g_fused)
        for a, b in zip(flat_b, flat_f):
            # bf16 activations: the two paths round at different points
            # (fused casts hidden+head once; unfused casts inside
            # lm_head), so grads agree only to bf16 resolution.
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=5e-3, atol=1e-3)


def test_fused_ce_under_sharded_train_step():
    """fused_ce composes with DP and tensor sharding on the virtual mesh
    (the GSPMD path the TPU bench would run): losses finite, decreasing,
    and matching the unfused step at init."""
    import dataclasses

    from ray_tpu.models.configs import llama_tiny
    from ray_tpu.parallel import RULES_DP, RULES_TP, MeshSpec, make_mesh
    from ray_tpu.train.step import transformer_train_step

    tokens = np.random.RandomState(3).randint(
        0, 512, (8, 33)).astype(np.int32)
    for spec, rules in ((MeshSpec(data=8), RULES_DP),
                        (MeshSpec(fsdp=4, tensor=2), RULES_TP)):
        mesh = make_mesh(spec)
        cfg = dataclasses.replace(llama_tiny(), fused_ce=True)
        ts = transformer_train_step(cfg, mesh, rules=rules,
                                    shift_inputs=True)
        params, opt_state = ts.init(jax.random.key(0))
        batch = ts.shard_batch({"tokens": tokens})

        base_cfg = llama_tiny()
        ts0 = transformer_train_step(base_cfg, mesh, rules=rules,
                                     shift_inputs=True)
        p0, _ = ts0.init(jax.random.key(0))
        l_fused = float(ts.eval_loss(params, batch))
        l_base = float(ts0.eval_loss(p0, batch))
        assert abs(l_fused - l_base) < 5e-2, (l_fused, l_base)

        losses = []
        for _ in range(3):
            params, opt_state, loss = ts.step(params, opt_state, batch)
            losses.append(float(loss))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
