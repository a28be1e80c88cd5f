"""Kimi Delta Attention (ops/kda.py) on the CPU at small sizes: the chunked
algorithm against the recurrence, the Pallas kernels (interpret mode) against
both, and the dispatch rule. The rule at a decay a head is
tests/test_kda_scalar.py, what a remat policy keeps of the kernels in a
traced stack tests/test_kda_remat.py; tests/test_kda_kernel_compile.py
compiles the kernels for the chip."""
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def _qkvgb(B, S, H, dk, dv, seed=0, decay=1.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (B, S, H, dk)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (B, S, H, dk)))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("S,chunk,sub", [(64, 16, 16), (100, 32, 16),
                                         (96, 64, 32), (256, 128, 32),
                                         (130, 128, 32)])
def test_kda_chunked_matches_recurrence(S, chunk, sub):
    """Outputs, final state and every input's gradient, across chunk sizes
    and lengths that are not a multiple of the chunk."""
    args = _qkvgb(2, S, 2, 16, 24, seed=S)
    chunked = jax.jit(lambda *a: kda.kda_chunked(*a, chunk=chunk, sub=sub))
    o1, s1 = jax.jit(kda.kda_recurrent)(*args)
    o2, s2 = chunked(*args)
    np.testing.assert_allclose(o2, o1, atol=2e-5 * float(jnp.abs(o1).max()))
    np.testing.assert_allclose(s2, s1, atol=2e-5 * float(jnp.abs(s1).max()))
    loss = lambda f: lambda *a: jnp.sum(f(*a)[0] ** 2)
    g1 = jax.jit(jax.grad(loss(kda.kda_recurrent),
                          argnums=(0, 1, 2, 3, 4)))(*args)
    g2 = jax.jit(jax.grad(loss(chunked), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(g2, g1):
        np.testing.assert_allclose(a, b, atol=5e-5 * float(jnp.abs(b).max()))


def test_kda_chunked_carries_state_and_strong_decay():
    """A sequence in two calls equals one call; a decay of e^-60 inside one
    chunk (past float32's range for e^G * e^-G from the chunk's start)
    stays exact thanks to the sub-block references."""
    q, k, v, g, beta = _qkvgb(1, 128, 2, 16, 16, seed=3, decay=0.5)
    g = g.at[:, 40:60].set(-3.0)  # 20 tokens of e^-3 each in a 128-chunk
    o, s = jax.jit(kda.kda_recurrent)(q, k, v, g, beta)
    a = [x[:, :64] for x in (q, k, v, g, beta)]
    b = [x[:, 64:] for x in (q, k, v, g, beta)]
    chunked = lambda **kw: jax.jit(functools.partial(kda.kda_chunked, **kw))
    o_a, s_a = chunked(chunk=32)(*a)
    o_b, s_b = chunked(chunk=32)(*b, initial_state=s_a)
    np.testing.assert_allclose(jnp.concatenate([o_a, o_b], 1), o, atol=1e-5)
    np.testing.assert_allclose(s_b, s, atol=1e-5)
    o128, _ = chunked(chunk=128, sub=32)(q, k, v, g, beta)
    np.testing.assert_allclose(o128, o, atol=1e-5)


@pytest.mark.parametrize("S,chunk,sub,dk,dv,init,strong", [
    (256, 128, 32, 128, 128, False, False),   # the chip's tile sizes
    (256, 128, 32, 128, 128, True, True),     # e^-60 inside a chunk
    (300, 128, 32, 16, 24, True, False),      # S not a multiple of the chunk
    (96, 32, 16, 16, 16, False, True),
    (64, 16, 16, 16, 24, True, False),        # one sub-block, no merge
])
def test_kda_kernels_match_recurrence_and_xla(S, chunk, sub, dk, dv, init,
                                              strong):
    """The Pallas kernels (interpret mode here) against the recurrence and
    the XLA body: outputs, final state, all five gradients and the initial
    state's, with a cotangent on the final state too."""
    q, k, v, g, beta = _qkvgb(1, S, 2, dk, dv, seed=S + dk,
                              decay=0.5 if strong else 1.0)
    if strong:  # 20 tokens of e^-3 each inside one chunk
        g = g.at[:, 40:60].set(-3.0)
    s0 = (jax.random.normal(jax.random.key(9), (1, 2, dk, dv)) if init
          else jnp.zeros((1, 2, dk, dv)))

    def run(f, **kw):
        def loss(q, k, v, g, beta, s0):
            o, s = f(q, k, v, g, beta, initial_state=s0, **kw)
            return jnp.sum(o ** 2) + jnp.sum(jnp.sin(s)), (o, s)
        (_, (o, s)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4, 5), has_aux=True))(
                q, k, v, g, beta, s0)
        return (o, s) + grads

    want = run(kda.kda_recurrent)
    xla = run(kda.kda_chunked_xla, chunk=chunk, sub=sub)
    got = run(kda.kda_chunked_pallas, chunk=chunk, sub=sub)
    for i, (a, b, c) in enumerate(zip(got, want, xla)):
        atol = (2e-5 if i < 2 else 5e-5) * float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=atol, err_msg=f"output {i}")
        np.testing.assert_allclose(a, c, atol=atol, err_msg=f"output {i}")


# sha256[:16] of the jaxpr a per-channel call (H_k = H_v, g rank 4) traced at
# the parent commit (PR 41's tree, this container's JAX, this file's
# `exact_matmuls`): `_per_channel_jaxpr` below, run on that tree.
PARENT_JAXPR = "9abd7e0b4ccccd71"


def _per_channel_jaxpr():
    """Loss and all six gradients through the kernels at 300 tokens (padded
    to three chunks), two heads of 128, bfloat16, an initial state: every
    equation outside and inside the two `pallas_call`s, source locations
    stripped."""
    sd = jax.ShapeDtypeStruct
    q = sd((1, 300, 2, 128), jnp.bfloat16)
    g, beta = sd((1, 300, 2, 128), jnp.float32), sd((1, 300, 2), jnp.float32)

    def loss(q, k, v, g, beta, s0):
        o, s = kda.kda_chunked_pallas(q, k, v, g, beta, chunk=128,
                                      initial_state=s0)
        return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(s)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=range(6)))(
        q, q, q, g, beta, sd((1, 2, 128, 128), jnp.float32)))
    return re.sub(r" at [^\s:]+:\d+", "", text)


def test_a_per_channel_call_traces_what_it_traced():
    """The scalar-decay bodies beside them leave the per-channel kernels as
    they were, operand for operand and equation for equation: the hybrid
    cell's call is the parent's."""
    text = _per_channel_jaxpr()
    assert text.count("pallas_call[") == 2
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENT_JAXPR


def test_decay_gradient_of_a_head_that_forgets_fast():
    """PR 41's `dg_noise` probe: bfloat16 q, k, v, four value heads on two
    key heads at A 16 and dt about 0.1 (a decay of e^-1.6 a token), dg
    summed over the tokens as it reaches `dt_bias`. The scalar body forms dG
    as the row sums less the column sums of ONE float32 [C, C] matrix, so a
    pair's term leaves dg between its two tokens exactly as it entered:
    within 2% of the float32 recurrence's (0.2-0.7% by seed, what the
    recurrence itself reads on the same bfloat16 inputs). The broadcast
    into the per-channel body writes the pair at its row and at its column
    through two bfloat16 products and reads 10-25% off."""
    S, d, Hk, Hv = 512, 128, 2, 4
    ks = jax.random.split(jax.random.key(7), 6)
    q = kda.l2_normalize(jax.random.normal(ks[0], (1, S, Hk, d)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (1, S, Hk, d)))
    v = jax.random.normal(ks[2], (1, S, Hv, d))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (1, S, Hv)) * 0.25 - 2.25)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, S, Hv)))
    wo = jax.random.normal(ks[5], (1, S, Hv, d))
    assert 0.08 < float(dt.mean()) < 0.13
    half = lambda a: a.astype(jnp.bfloat16)

    def d_dt_bias(body, *qkv):
        def loss(g):
            return jnp.sum(body(*qkv, g, beta)[0].astype(jnp.float32) * wo)
        return jax.jit(jax.grad(loss))(-16.0 * dt).sum(1)[0]

    def broadcast(q, k, v, g, beta):
        q, k, g = kda._per_channel(q, k, v, g)
        return kda.kda_chunked_pallas(q, k, v, g, beta)

    want = d_dt_bias(kda.kda_recurrent, q, k, v)
    rel = lambda got: float(jnp.linalg.norm(got - want)
                            / jnp.linalg.norm(want))
    scalar = rel(d_dt_bias(kda.kda_chunked_pallas, half(q), half(k), half(v)))
    old = rel(d_dt_bias(broadcast, half(q), half(k), half(v)))
    assert scalar < 0.02, (scalar, old)
    assert old > 0.05, (scalar, old)


def test_kda_dispatch_rule():
    """`use_kernels` is a pure function of platform and shapes; on the CPU
    `kda_chunked` is the XLA body, and says so in the phase table."""
    from ray_tpu.util import tracing

    assert kda.use_kernels("tpu", 128, 128, 128, on_mesh=False)
    assert kda.use_kernels("tpu", 256, 128, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 128, 256, on_mesh=False)
    assert not kda.use_kernels("cpu", 128, 128, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 16, 128, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 16, 128, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 128, 32, on_mesh=False)
    assert not kda.use_kernels("tpu", 128, 128, 128, on_mesh=True)
    count = lambda n: tracing.phase_table().get(n, {"count": 0})["count"]
    before = count("kda.core.xla"), count("kda.core.pallas")
    args = _qkvgb(1, 128, 1, 128, 128)
    np.testing.assert_array_equal(
        kda.kda_chunked(*args)[0], kda.kda_chunked_xla(*args)[0])
    assert (count("kda.core.xla"), count("kda.core.pallas")) == (
        before[0] + 1, before[1])
