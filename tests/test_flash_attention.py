"""Flash-attention kernel vs the reference XLA implementation.

Runs the Pallas kernels in interpret mode on CPU (same code path the TPU
compiles), checking forward values and gradients, causal + GQA variants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention


def _rand_qkv(key, B, S, H, KVH, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, KVH, D), dtype)
    v = jax.random.normal(kv, (B, S, KVH, D), dtype)
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


def test_attn_impl_flag_forces_xla(monkeypatch):
    """RTPU_ATTN_IMPL selects the implementation: 'xla' keeps the compiled
    program free of Pallas custom calls, 'flash' forces the kernel. On the
    CPU test platform 'auto' picks XLA anyway, so assert the dispatch
    decision itself via use_flash resolution against a stub."""
    import ray_tpu.ops.attention as att

    called = {}

    def fake_flash(q, k, v, **kw):
        called["flash"] = True
        return att.reference_attention(q, k, v, causal=kw.get("causal", True))

    import ray_tpu.ops.flash_attention as fa
    monkeypatch.setattr(fa, "flash_attention", fake_flash)
    B, S, H, D = 1, 16, 2, 8
    q = jnp.ones((B, S, H, D), jnp.float32)

    monkeypatch.setenv("RTPU_ATTN_IMPL", "flash")
    att.attention(q, q, q, causal=True)
    assert called.pop("flash", False)

    monkeypatch.setenv("RTPU_ATTN_IMPL", "xla")
    att.attention(q, q, q, causal=True)
    assert "flash" not in called


def test_attn_impl_flag_bad_value_warns(monkeypatch):
    import warnings

    import ray_tpu.ops.attention as att

    monkeypatch.setenv("RTPU_ATTN_IMPL", "falsh")
    monkeypatch.setattr(att, "_warned_bad_impl", False)
    q = jnp.ones((1, 8, 2, 8), jnp.float32)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        att.attention(q, q, q, causal=True)
    assert any("RTPU_ATTN_IMPL" in str(x.message) for x in w)


def test_flash_runs_per_shard_on_a_multi_device_mesh():
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
            return att.attention(q, k, v, causal=True, use_flash=True)

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
