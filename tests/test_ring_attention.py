"""Ring/Ulysses sequence-parallel attention vs the dense reference.

Runs on the virtual 8-device CPU mesh (conftest) through real shard_map +
ppermute/all_to_all paths — the same program a TPU `seq` axis executes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.ring_attention import ring_attention
from ray_tpu.ops.ulysses_attention import ulysses_attention

SP = 4


def _mesh():
    return Mesh(np.array(jax.devices()[:SP]), ("sp",))


def _rand(key, B, S, H, KVH, D):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (B, S, H, D)),
            jax.random.normal(kk, (B, S, KVH, D)),
            jax.random.normal(kv, (B, S, KVH, D)))


@pytest.mark.parametrize("causal", [True, False])
def test_ring_forward(causal):
    B, S, H, KVH, D = 1, 256, 2, 2, 64
    q, k, v = _rand(jax.random.key(0), B, S, H, KVH, D)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal, block=64),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = jax.jit(fn)(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_grad_matches_reference():
    B, S, H, KVH, D = 1, 256, 2, 1, 32
    q, k, v = _rand(jax.random.key(1), B, S, H, KVH, D)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True, block=64),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gr, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_forward(causal):
    B, S, H, KVH, D = 1, 256, 4, 4, 32
    q, k, v = _rand(jax.random.key(2), B, S, H, KVH, D)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    out = jax.jit(fn)(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ulysses_grad():
    B, S, H, KVH, D = 1, 128, 4, 4, 32
    q, k, v = _rand(jax.random.key(3), B, S, H, KVH, D)
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    uly = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    ga = jax.jit(jax.grad(lambda q: jnp.sum(uly(q, k, v) ** 2)))(q)
    gb = jax.grad(
        lambda q: jnp.sum(reference_attention(q, k, v, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               atol=1e-3, rtol=1e-3)


def test_train_step_with_seq_axis():
    """Full sharded train step on a (data=2, seq=2, tensor=2) mesh: the
    model's attention dispatch embeds ring attention via shard_map and the
    loss/step still run end-to-end (context parallelism as a rule-table
    choice, not a model change)."""
    from ray_tpu.models.configs import llama_tiny
    from ray_tpu.parallel import MeshSpec, RULES_TP, make_mesh
    from ray_tpu.train.step import transformer_train_step

    mesh = make_mesh(MeshSpec(data=2, seq=2, tensor=2),
                     devices=jax.devices()[:8])
    cfg = llama_tiny()
    ts = transformer_train_step(cfg, mesh, rules=RULES_TP)
    params, opt_state = ts.init(jax.random.key(0))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (4, 64), dtype=np.int32)
    batch = ts.shard_batch({"tokens": tokens})
    params, opt_state, loss = ts.step(params, opt_state, batch)
    assert np.isfinite(float(loss))

    # Same loss as a single-device (no seq axis) run on identical inputs.
    mesh1 = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    ts1 = transformer_train_step(cfg, mesh1, rules=RULES_TP)
    params1, opt1 = ts1.init(jax.random.key(0))
    l1 = ts1.eval_loss(params1, {"tokens": tokens})
    params_f, _ = ts.init(jax.random.key(0))  # fresh (pre-step) params
    l0 = ts.eval_loss(params_f, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-3)
