"""What a remat policy keeps of the delta-rule kernels (ops/kda.py) in a
traced stack, on the CPU through the kernels in interpret mode: a KDA stack
under both policies, and a Gated DeltaNet stack (the scalar-decay kernels)
under the policy its cell runs. Either policy keeps the kernels' named
residuals, so the forward kernel runs once a layer. The cores themselves are
tests/test_kda.py and tests/test_kda_scalar.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import kimi_linear_tiny, qwen3_next_tiny
from ray_tpu.ops import kda
from test_kda_scalar import _outer_avals

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def _kernel_calls(jaxpr, times=1, out=None):
    """pallas_calls of a jaxpr by operand signature, a call inside a scan
    counted once per iteration (tests/test_models.py does it for flash)."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            sig = f"{len(eqn.invars)}in_{len(eqn.outvars)}out"
            out[sig] = out.get(sig, 0) + times
        inner = times * (eqn.params["length"]
                         if eqn.primitive.name == "scan" else 1)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _kernel_calls(sub, inner, out)
    return out


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_keeps_the_kda_kernel_residuals(monkeypatch, policy):
    """The traced gradient of a KDA stack through the kernels: under either
    policy the forward kernel (6 in / 4 out) runs once a layer, its o,
    states and inverses being named residuals. The backward kernel (9 in /
    6 out) once. Neither has a flash kernel's signature
    (chipbench/reduce/xplane.py names kernels by it). Gradients are those
    of the XLA body. Two layers, a dense one and an expert one: as many as
    "a layer" needs."""
    cfg = kimi_linear_tiny(n_layers=2, moe_held=(0, 16), remat=True,
                           remat_policy=policy, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    # A new function each time: jax caches a trace by the function's identity.
    grad = lambda: jax.grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True))
    assert _kernel_calls(jax.make_jaxpr(grad())(params).jaxpr) == {}
    g_xla = jax.jit(grad())(params)
    monkeypatch.setattr(kda, "use_kernels", lambda *a, **kw: True)
    calls = _kernel_calls(jax.make_jaxpr(grad())(params).jaxpr)
    assert calls == {"6in_4out": 2, "9in_6out": 2}
    if policy == "dots":
        for a, b in zip(jax.tree.leaves(jax.jit(grad())(params)),
                        jax.tree.leaves(g_xla)):
            np.testing.assert_allclose(a, b, atol=1e-5 + 1e-4 * float(
                jnp.abs(b).max()))


def test_the_stack_takes_the_scalar_kernels_under_full_remat(monkeypatch):
    """A scanned stack's gradient through the kernels (interpret mode) under
    the cell's remat policy: two DeltaNet layers, one scan, run the scalar
    forward kernel once each (6 in / 4 out; `full` keeps the core's named
    residuals) and the backward once (9 in / 6 out), every call reading g
    as [B, S, H_v]; the gradients are the XLA body's."""
    cfg = qwen3_next_tiny(n_layers=2, remat=True, remat_policy="full",
                          dtype=jnp.float32)
    assert cfg.stack_plan() == (((("gdn", "moe"),), 2),)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 33), 0, cfg.vocab_size)
    # A new function each time: jax caches a trace by the function's identity.
    grad = lambda: jax.grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True))
    g_xla = jax.jit(grad())(params)
    monkeypatch.setattr(kda, "use_kernels", lambda *a, **kw: True)
    jaxpr = jax.make_jaxpr(grad())(params).jaxpr
    calls = _kernel_calls(jaxpr)
    assert calls["6in_4out"] == 2 and calls["9in_6out"] == 2, calls
    for call in _outer_avals(jaxpr)[1]:
        if len(call.invars) in (6, 9):  # the core's, not flash's
            assert call.invars[3].aval.shape == (2, 32, cfg.gdn_v_heads)
    for a, b in zip(jax.tree.leaves(jax.jit(grad())(params)),
                    jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(a, b, atol=1e-5 + 1e-4 * float(
            jnp.abs(b).max()))
