"""The delta rule at one decay a head (ops/kda.py, a rank-3 `g`: what
Gated DeltaNet layers run) on the CPU at small sizes: the chunked core, XLA
body and scalar-decay kernels (interpret mode), against the recurrence for
H_v = H_k and H_v = 2 H_k; what a scalar-decay call makes outside its
kernels; and a decay constant over the channels against the scalar one. The
per-channel rule is tests/test_kda.py, the kernels in a remat'd stack
tests/test_kda_remat.py, compiled for the chip
tests/test_kda_kernel_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def _core_inputs(S, Hk, Hv, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q = kda.l2_normalize(jax.random.normal(ks[0], (2, S, Hk, d)))
    k = kda.l2_normalize(jax.random.normal(ks[1], (2, S, Hk, d)))
    v = jax.random.normal(ks[2], (2, S, Hv, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (2, S, Hv), minval=-6.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, S, Hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("body,S,init,strong", [
    ("xla", 80, False, False),
    ("pallas", 256, False, False),
    ("pallas", 300, True, False),   # a state to start from, a ragged end
    ("pallas", 256, True, True),    # a head that forgets inside a few tokens
])
@pytest.mark.parametrize("Hk,Hv", [(2, 2), (2, 4)])
def test_the_scalar_decay_core_is_the_recurrence(body, S, init, strong, Hk,
                                                 Hv):
    """The chunked core at one decay a head, for H_v = H_k and H_v = 2 H_k
    (key head i serving value heads 2i and 2i + 1), is `kda_recurrent`:
    outputs, final state and the gradients of all five inputs and of the
    initial state, through the XLA body and through the scalar-decay kernels
    (interpret mode), which are held to the XLA body too. `strong`: value
    head 1 decays by e^-4 a token, e^-128 inside one sub-block of 32, past
    the per-channel bodies' cap of e^80 (their pairs late in the sub-block
    come out e^-7 for e^-4, so that case is held to the recurrence alone);
    the scalar body's e^(G_t - G_s) has nothing to cap."""
    d, chunk = (16, 16) if body == "xla" else (128, 128)
    fn = kda.kda_chunked_xla if body == "xla" else kda.kda_chunked_pallas
    q, k, v, g, beta = _core_inputs(S, Hk, Hv, d)
    if strong:
        g = g.at[:, :, 1].set(-4.0)
    s0 = (jax.random.normal(jax.random.key(9), (2, Hv, d, d)) if init
          else jnp.zeros((2, Hv, d, d)))
    args = (q, k, v, g, beta, s0)

    def run(f, **kw):
        def loss(q, k, v, g, beta, s0):
            o, s = f(q, k, v, g, beta, initial_state=s0, **kw)
            return jnp.sum(jnp.sin(o)) + jnp.sum(s * s), (o, s)
        return jax.jit(jax.value_and_grad(loss, argnums=range(6),
                                          has_aux=True))(*args)

    (_, (o, s)), grads = run(fn, chunk=chunk)
    (_, (o_r, s_r)), grads_r = run(kda.kda_recurrent)
    assert o.shape == (2, S, Hv, d) and s.shape == (2, Hv, d, d)
    np.testing.assert_allclose(o, o_r, atol=2e-5)
    np.testing.assert_allclose(s, s_r, atol=2e-5)
    for got, want in zip(grads, grads_r):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5 * max(
            1.0, float(jnp.abs(want).max())))
    if body == "pallas" and not strong:
        (_, (o_x, s_x)), grads_x = run(kda.kda_chunked_xla, chunk=chunk)
        for got, want in zip((o, s) + grads, (o_x, s_x) + grads_x):
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=5e-5 * max(
                1.0, float(jnp.abs(want).max())))
    # Value heads 0 and 1 read key head 0: head i + H_k would differ.
    if Hv > Hk:
        alone, _ = kda.kda_recurrent(
            q[:, :, :1], k[:, :, :1], v[:, :, 1:2], g[:, :, 1:2],
            beta[:, :, 1:2], initial_state=s0[:, 1:2])
        np.testing.assert_allclose(o[:, :, 1:2], alone, atol=2e-5)


def _outer_avals(jaxpr, out=None):
    """(shape, dtype) of every variable an equation outside the kernels
    makes (a `pallas_call`'s own results counted, its body not), and the
    `pallas_call` equations themselves."""
    out = ([], []) if out is None else out
    for eqn in jaxpr.eqns:
        out[0].extend((tuple(v.aval.shape), str(v.aval.dtype))
                      for v in eqn.outvars)
        if eqn.primitive.name == "pallas_call":
            out[1].append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _outer_avals(sub, out)
    return out


def test_a_scalar_decay_call_takes_its_operands_as_they_lie():
    """A rank-3 call's program, forward and gradient: nothing [B,S,H_v,d_k]
    in float32 (g broadcast over the channels, or its gradient before the
    sum), no q or k repeated over the value heads (nothing [B,S,H_v,d_k] or
    [B,S,H_v d_k] at all: d_v differs here), and two `pallas_call`s that
    read g as [B,S,H_v] and write dg as dbeta, [B,H_v,1,S]."""
    B, S, Hk, Hv, dk, dv = 1, 256, 2, 4, 128, 256
    sd = jax.ShapeDtypeStruct
    q, v = sd((B, S, Hk, dk), jnp.bfloat16), sd((B, S, Hv, dv), jnp.bfloat16)
    g = sd((B, S, Hv), jnp.float32)

    def loss(q, k, v, g, beta):
        o, _ = kda.kda_chunked_pallas(q, k, v, g, beta, chunk=128)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(5)))(q, q, v, g, g)
    avals, calls = _outer_avals(jaxpr.jaxpr)
    shapes = {sh for sh, _ in avals}
    assert (B, S, Hv, dk) not in shapes and (B, S, Hv * dk) not in shapes
    assert [len(c.invars) for c in calls] == [6, 9]
    for call in calls:
        ins = [tuple(x.aval.shape) for x in call.invars]
        assert ins[:5] == [(B, S, Hk * dk)] * 2 + [(B, S, Hv * dv)] + [
            (B, S, Hv)] * 2
    outs = [tuple(x.aval.shape) for x in calls[1].outvars]
    assert outs[:2] == [(B, S, Hk * dk)] * 2          # dq, dk a KEY head
    assert outs[3:5] == [(B, Hv, 1, S)] * 2           # dg as dbeta: rows
    # The broadcast it replaces, for scale: a per-channel call on the same
    # rule has both.
    def broadcast(q, k, v, g, beta):
        q, k, g = kda._per_channel(q, k, v, g)
        return loss(q, k, v, g, beta)

    old = jax.make_jaxpr(jax.grad(broadcast, argnums=range(5)))(q, q, v, g, g)
    assert ((B, S, Hv, dk), "float32") in _outer_avals(old.jaxpr)[0]


def test_a_decay_constant_over_channels_is_the_scalar_decay():
    """A rank-4 g that is the same number in every channel gives what the
    rank-3 g gives, bit for bit (the scalar call IS that broadcast), and a
    per-channel call traces what it traced: `_per_channel` hands its
    operands back as they are."""
    q, k, v, g, beta = _core_inputs(64, 2, 2, 16)
    g4 = jnp.broadcast_to(g[..., None], g.shape + (16,))
    for fn in (kda.kda_chunked_xla, kda.kda_recurrent):
        o3, s3 = fn(q, k, v, g, beta)
        o4, s4 = fn(q, k, v, g4, beta)
        np.testing.assert_array_equal(o3, o4)
        np.testing.assert_array_equal(s3, s4)
    same = kda._per_channel(q, k, v, g4)
    assert same[0] is q and same[1] is k and same[2] is g4
