"""KV-cache decoding (models/generate.py): the cached incremental path
must produce EXACTLY the tokens the naive re-run-the-full-forward loop
produces — the strongest equivalence a cache implementation can offer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import llama_tiny
from ray_tpu.models.generate import KVCache, decode_step, generate, prefill


def _naive_greedy(params, tokens, cfg, n):
    toks = tokens
    for _ in range(n):
        logits = tfm.forward(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1], -1).astype(toks.dtype)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    return toks


@pytest.fixture(scope="module")
def tiny():
    cfg = llama_tiny(remat=False)
    params = tfm.init_params(jax.random.key(0), cfg)
    return cfg, params


def test_greedy_matches_naive_forward(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(1), (2, 7), 0,
                                cfg.vocab_size, jnp.int32)
    fast = generate(params, tokens, cfg, max_new_tokens=6)
    slow = _naive_greedy(params, tokens, cfg, 6)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


def test_prefill_logits_match_forward(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(2), (3, 5), 0,
                                cfg.vocab_size, jnp.int32)
    logits, cache = prefill(params, tokens, cfg, max_len=16)
    full = tfm.forward(params, tokens, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               atol=2e-2, rtol=2e-2)
    assert int(cache.pos) == 5 and cache.k.shape[2] == 16


def test_decode_step_advances_cache(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(3), (2, 4), 0,
                                cfg.vocab_size, jnp.int32)
    logits, cache = prefill(params, tokens, cfg, max_len=8)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache2 = decode_step(params, cache, tok, cfg)
    assert int(cache2.pos) == 5
    assert logits2.shape == (2, cfg.vocab_size)
    # The appended K row must be nonzero where the old cache had padding.
    assert float(jnp.abs(cache2.k[:, :, 4]).sum()) > 0
    assert float(jnp.abs(cache.k[:, :, 4]).sum()) == 0


def test_eos_freezes_rows(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(4), (2, 3), 0,
                                cfg.vocab_size, jnp.int32)
    out = generate(params, tokens, cfg, max_new_tokens=8, eos_id=0)
    arr = np.asarray(out)
    for row in arr:
        gen = row[3:]
        hits = np.flatnonzero(gen == 0)
        if hits.size:  # everything after the first eos stays eos
            assert (gen[hits[0]:] == 0).all()


def test_sampled_generation_shape_and_jit(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(5), (2, 4), 0,
                                cfg.vocab_size, jnp.int32)
    gen = jax.jit(lambda p, t, r: generate(
        p, t, cfg, max_new_tokens=5, temperature=0.8, top_k=5, rng=r))
    out = gen(params, tokens, jax.random.key(7))
    assert out.shape == (2, 9)
    assert (np.asarray(out[:, :4]) == np.asarray(tokens)).all()
    # Sampling with a different key changes the continuation.
    out2 = gen(params, tokens, jax.random.key(8))
    assert not np.array_equal(np.asarray(out), np.asarray(out2))


def test_gqa_cache_decoding():
    """n_kv_heads=1 (MQA) exercises the extreme grouping; the default
    tiny config (4 heads / 2 kv) covers plain GQA in the tests above."""
    cfg = llama_tiny(remat=False, n_heads=4, n_kv_heads=1)  # MQA
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(6), (2, 6), 0,
                                cfg.vocab_size, jnp.int32)
    fast = generate(params, tokens, cfg, max_new_tokens=4)
    slow = _naive_greedy(params, tokens, cfg, 4)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(slow))


def test_decode_step_overflow_raises_eagerly(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(9), (1, 3), 0,
                                cfg.vocab_size, jnp.int32)
    logits, cache = prefill(params, tokens, cfg, max_len=4)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    _, cache = decode_step(params, cache, tok, cfg)  # fills slot 3
    with pytest.raises(ValueError, match="cache full"):
        decode_step(params, cache, tok, cfg)


def test_ragged_batch_matches_per_row_naive(tiny):
    """generate_ragged: mixed prompt lengths in ONE batch produce exactly
    the per-row naive greedy continuations (right-padding + per-row cache
    positions must never leak pad tokens into attention)."""
    from ray_tpu.models.generate import generate_ragged

    cfg, params = tiny
    prompts = [[5, 9, 2], [7, 1, 3, 3, 8, 1], [4]]
    S = 8
    toks = np.zeros((3, S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    out = generate_ragged(params, jnp.asarray(toks), lengths, cfg,
                          max_new_tokens=5)
    assert out.shape == (3, 5)
    for i, p in enumerate(prompts):
        exp = _naive_greedy(params, jnp.asarray([p], jnp.int32), cfg, 5)
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(exp)[0, len(p):])


def test_ragged_per_row_temperature(tiny):
    """temperature as a [B] vector: greedy rows are deterministic while
    sampled rows vary with the key."""
    from ray_tpu.models.generate import generate_ragged

    cfg, params = tiny
    toks = jax.random.randint(jax.random.key(3), (2, 6), 0,
                              cfg.vocab_size, jnp.int32)
    lengths = jnp.asarray([6, 6], jnp.int32)
    temps = jnp.asarray([0.0, 1.2], jnp.float32)
    o1 = generate_ragged(params, toks, lengths, cfg, max_new_tokens=6,
                         temperature=temps, rng=jax.random.key(1))
    o2 = generate_ragged(params, toks, lengths, cfg, max_new_tokens=6,
                         temperature=temps, rng=jax.random.key(2))
    np.testing.assert_array_equal(np.asarray(o1[0]), np.asarray(o2[0]))
    assert not np.array_equal(np.asarray(o1[1]), np.asarray(o2[1]))
    # Greedy row equals the scalar-path greedy generation.
    exp = _naive_greedy(params, toks[:1], cfg, 6)
    np.testing.assert_array_equal(np.asarray(o1[0]), np.asarray(exp)[0, 6:])


def test_ragged_one_compile_for_mixed_batches(tiny):
    """The jitted ragged program is reused across batch compositions with
    different length mixes (same padded shape)."""
    from ray_tpu.models.generate import generate_ragged

    cfg, params = tiny
    gen = jax.jit(lambda p, t, l: generate_ragged(p, t, l, cfg,
                                                  max_new_tokens=3))
    t1 = jnp.zeros((2, 6), jnp.int32).at[0, :2].set(5).at[1, :6].set(3)
    o1 = gen(params, t1, jnp.asarray([2, 6], jnp.int32))
    o2 = gen(params, t1, jnp.asarray([4, 1], jnp.int32))
    assert o1.shape == o2.shape == (2, 3)
    assert gen._cache_size() == 1


def test_generate_under_tensor_sharded_mesh():
    """Multi-chip inference: generate() runs under a tensor-parallel mesh
    with GSPMD-sharded params and produces EXACTLY the unsharded greedy
    tokens (collectives inserted by XLA, same layer code as training)."""
    from ray_tpu.parallel import RULES_TP, MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import (logical_to_mesh_spec,
                                           sharding_ctx)

    # float32: in bfloat16 a sharded sum rounds apart from the unsharded one
    # far enough to flip a near-tie of random weights' logits (1 seed in 6).
    cfg = llama_tiny(remat=False, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 6), 0,
                                cfg.vocab_size, jnp.int32)
    expected = np.asarray(generate(params, tokens, cfg, max_new_tokens=4))

    mesh = make_mesh(MeshSpec(fsdp=4, tensor=2))
    specs = tfm.param_logical_specs(cfg)
    sharded = jax.tree.map(
        lambda p, s: jax.device_put(
            p, jax.NamedSharding(mesh, logical_to_mesh_spec(s, RULES_TP,
                                                            mesh))),
        params, specs)
    with sharding_ctx(mesh, RULES_TP):
        out = jax.jit(
            lambda p, t: generate(p, t, cfg, max_new_tokens=4))(sharded,
                                                                tokens)
    np.testing.assert_array_equal(np.asarray(out), expected)
