"""The Mamba-2 core's Pallas kernels (ops/ssd.py, interpret mode on the CPU)
against the XLA body and the recurrence, and what a remat policy keeps of
them in a traced Mamba-2 stack. The chunked form itself is
tests/test_ssd.py. A file of its own: `--dist loadfile` gives a file to one
worker, and these eight cases are half of the core's seconds."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import granite_hybrid_tiny
from ray_tpu.ops import ssd
from test_kda_remat import _kernel_calls
from test_ssd import _case

pytestmark = pytest.mark.usefixtures("exact_matmuls")


_NAMES = ("x", "dt", "A", "B", "C", "D", "s0")


@pytest.mark.parametrize("name,kw,chunk", [
    # The cell's head shape cut in count: heads of 64, state 128, one
    # group, chunks of 256 (two lane groups of two heads a grid step).
    ("cell_heads", dict(B=1, S=512, H=4, P=64, G=1, N=128, state=False), 256),
    ("two_groups", dict(B=2, S=256, H=4, P=64, G=2, N=128), 128),
    ("ragged", dict(B=1, S=300, H=2, P=64, G=1, N=128, state=False), 128),
    ("initial_state", dict(B=1, S=256, H=2, P=64, G=1, N=128), 128),
    ("decay_past_e88", dict(B=1, S=256, H=2, P=64, G=1, N=128,
                            dt_scale=12.0), 128),
    ("heads_of_128", dict(B=1, S=256, H=2, P=128, G=1, N=128), 128),
])
def test_kernels_match_xla_and_recurrence(name, kw, chunk):
    """The Pallas kernels (interpret mode here) against the XLA body and the
    recurrence: y, the final state and every gradient (x, dt, A, B, C, D,
    the initial state), with a cotangent on the final state too."""
    c = _case(6, **kw)
    if c["s0"] is None:
        c["s0"] = jnp.zeros((kw["B"], kw["H"], kw["P"], kw["N"]))
    if name == "decay_past_e88":
        assert float(jnp.sum((c["dt"] * c["A"])[:, :chunk], axis=1).min()
                     ) < -500.0

    def run(fn):
        def loss(*args):
            y, s = fn(*args[:6], initial_state=args[6])
            w = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
            return jnp.sum(y * w) + jnp.sum(jnp.sin(s)), (y, s)
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=range(7), has_aux=True))(*(c[n] for n in _NAMES))
        return out + grads

    got = run(functools.partial(ssd.ssd_chunked_pallas, chunk=chunk))
    xla = run(functools.partial(ssd.ssd_chunked_xla, chunk=chunk))
    want = run(ssd.ssd_recurrent)
    for n, a, b, r in zip(("y", "state") + _NAMES, got, xla, want):
        assert np.isfinite(a).all(), n
        # dA is a sum of terms that cancel, the more the stronger the decay:
        # the two oracles differ by 4e-3 of it in the last case, by 2e-4 in
        # the others.
        tol = (3e-2 if name == "decay_past_e88" else 1e-3) if n == "A" \
            else 1e-4
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(a, r, err_msg=n, atol=tol * scale)
        np.testing.assert_allclose(a, b, err_msg=n, atol=2 * tol * scale)


@pytest.mark.parametrize("policy", ["dots", "full"])
def test_remat_keeps_the_ssd_kernel_residuals(monkeypatch, policy):
    """The traced gradient of a two-layer `mamba2` stack through the kernels:
    under either policy the forward kernel (7 in / 3 out) runs once a layer,
    its y and chunk-start states being named residuals. The backward kernel
    (9 in / 7 out) once. Neither has a flash or a KDA
    kernel's signature (chipbench/reduce/xplane.py names kernels by it).
    Gradients are those of the XLA body, and the traced calls count as
    `ssd.core.pallas`."""
    from ray_tpu.util import tracing

    cfg = granite_hybrid_tiny(n_layers=2, mamba_layers=(1, 2), remat=True,
                              remat_policy=policy, dtype=jnp.float32)
    params = tfm.init_params(jax.random.key(0), cfg)
    toks = jax.random.randint(jax.random.key(1), (2, 41), 0, cfg.vocab_size)
    # A new function each time: jax caches a trace by the function's identity.
    grad = lambda: jax.grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True))
    assert _kernel_calls(jax.make_jaxpr(grad())(params).jaxpr) == {}
    g_xla = jax.jit(grad())(params)
    monkeypatch.setattr(ssd, "use_kernels", lambda *a, **kw: True)
    count = lambda: tracing.phase_table().get(
        "ssd.core.pallas", {"count": 0})["count"]
    before = count()
    calls = _kernel_calls(jax.make_jaxpr(grad())(params).jaxpr)
    assert calls == {"7in_3out": 2, "9in_7out": 2}
    assert count() > before
    if policy == "dots":
        for a, b in zip(jax.tree.leaves(jax.jit(grad())(params)),
                        jax.tree.leaves(g_xla)):
            np.testing.assert_allclose(a, b, atol=1e-5 + 1e-4 * float(
                jnp.abs(b).max()))
