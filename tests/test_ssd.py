"""The Mamba-2 core (ops/ssd.py) on the CPU: the chunked form against the
token-by-token recurrence, forward and gradients, at lengths that are no
multiple of the chunk, with an initial state, several groups, and decays
strong enough that an unmasked exp would overflow; the dispatch rule and
the phase-table count; and reduce/ssd_counts.py against a hand count. The
Pallas kernels (interpret mode) against both, and what a remat policy keeps
of them in a traced Mamba-2 stack, are tests/test_ssd_kernels.py;
tests/test_kda_kernel_compile.py compiles the kernels for the chip."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def _case(seed, B=2, S=37, H=4, P=8, G=2, N=16, dt_scale=1.0, state=True):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (B, S, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (B, S, H))) * dt_scale,
        A=-jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7)),
        B=jax.random.normal(k[3], (B, S, G, N)),
        C=jax.random.normal(k[4], (B, S, G, N)),
        D=jax.random.normal(k[5], (H,)),
        s0=jax.random.normal(k[6], (B, H, P, N)) if state else None)


def _run(fn, c):
    """One jitted program a call: op by op the chunked form's small ops
    compile one at a time (ROADMAP D11)."""
    return jax.jit(fn)(c["x"], c["dt"], c["A"], c["B"], c["C"], c["D"],
                       initial_state=c["s0"])


@pytest.mark.parametrize("name,kw,chunk", [
    ("ragged_two_groups", dict(S=37), 16),          # 37 = 2 x 16 + 5
    ("one_group_no_state", dict(S=48, G=1, state=False), 16),
    ("one_short_chunk", dict(S=5, G=4), 8),
    ("every_head_a_group", dict(S=33, G=4), 32),
])
def test_chunked_matches_recurrent(name, kw, chunk):
    c = _case(1, **kw)
    y, s = _run(functools.partial(ssd.ssd_chunked, chunk=chunk), c)
    y_r, s_r = _run(ssd.ssd_recurrent, c)
    assert y.shape == c["x"].shape and s.shape == s_r.shape
    np.testing.assert_allclose(y, y_r, rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_r, rtol=1e-4, atol=2e-4)


def test_gradients_match_recurrent():
    c = _case(2)
    names = ("x", "dt", "A", "B", "C", "D", "s0")

    def loss(fn):
        def f(*args):
            y, s = fn(*args[:6], initial_state=args[6])
            w = jnp.cos(jnp.arange(y.size, dtype=jnp.float32)).reshape(y.shape)
            return jnp.sum(y * w) + jnp.sum(s * s)
        return jax.grad(f, argnums=range(7))(*(c[n] for n in names))

    got = loss(functools.partial(ssd.ssd_chunked, chunk=16))
    want = loss(ssd.ssd_recurrent)
    for n, a, b in zip(names, got, want):
        assert np.isfinite(a).all(), n
        np.testing.assert_allclose(a, b, err_msg=n,
                                   atol=5e-4 * float(jnp.abs(b).max()))


def test_strong_decay_does_not_overflow():
    """A chunk decays by far more than e^88: exp(G_i - G_j) above the
    diagonal would be inf (and inf * 0 NaN in the backward) were the
    difference not masked before the exp."""
    c = _case(3, S=64, dt_scale=12.0)
    per_chunk = jnp.sum((c["dt"] * c["A"])[:, :32], axis=1)
    assert float(per_chunk.min()) < -500.0
    fn = functools.partial(ssd.ssd_chunked, chunk=32)
    y, s = _run(fn, c)
    y_r, s_r = _run(ssd.ssd_recurrent, c)
    np.testing.assert_allclose(y, y_r, rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(s, s_r, rtol=1e-4, atol=2e-4)
    g = jax.grad(lambda dt: jnp.sum(_run(fn, dict(c, dt=dt))[0] ** 2))(c["dt"])
    g_r = jax.grad(lambda dt: jnp.sum(
        _run(ssd.ssd_recurrent, dict(c, dt=dt))[0] ** 2))(c["dt"])
    assert np.isfinite(g).all()
    np.testing.assert_allclose(g, g_r, atol=1e-3 * float(jnp.abs(g_r).max()))


def test_state_carries_across_calls():
    """Two calls, the second from the first's final state, equal one call
    over the whole sequence: what a decode step and a packed-document
    reset will build on."""
    c = _case(4, S=40, state=False)
    fn = functools.partial(ssd.ssd_chunked, chunk=16)
    y, s = _run(fn, c)
    cut = lambda a, sl: a if a is None or a.ndim < 3 else a[:, sl]
    first = {n: cut(a, slice(0, 24)) for n, a in c.items()}
    y1, s1 = _run(fn, first)
    second = dict({n: cut(a, slice(24, 40)) for n, a in c.items()}, s0=s1)
    y2, s2 = _run(fn, second)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y, atol=2e-4)
    np.testing.assert_allclose(s2, s, atol=2e-4)


def test_dispatch_rule():
    """`use_kernels` is a pure function of platform and shapes (the CPU, a
    mesh, a narrow state or chunk, heads that fill no lane group, states
    past the scratch -> XLA); on the CPU `ssd_chunked` is the XLA body."""
    cell = dict(P=64, N=128, chunk=256, heads=64, groups=1, on_mesh=False)
    assert ssd.use_kernels("tpu", **cell)
    assert ssd.use_kernels("tpu", **dict(cell, P=128, heads=3))
    assert ssd.use_kernels("tpu", **dict(cell, groups=8, chunk=128))
    assert not ssd.use_kernels("cpu", **cell)
    assert not ssd.use_kernels("tpu", **dict(cell, on_mesh=True))
    assert not ssd.use_kernels("tpu", **dict(cell, N=64))
    assert not ssd.use_kernels("tpu", **dict(cell, chunk=64))
    assert not ssd.use_kernels("tpu", **dict(cell, heads=63))
    assert not ssd.use_kernels("tpu", **dict(cell, heads=6, groups=2))
    assert not ssd.use_kernels("tpu", **dict(cell, P=96))
    assert not ssd.use_kernels("tpu", **dict(cell, heads=256))
    assert [ssd.heads_per_step(R, P) for R, P in
            ((64, 64), (2, 64), (6, 64), (3, 128), (8, 128), (4, 32))] == [
                8, 2, 2, 1, 4, 4]
    c = _case(7, S=32, H=2, P=64, G=1, N=128, state=False)
    np.testing.assert_array_equal(
        _run(functools.partial(ssd.ssd_chunked, chunk=128), c)[0],
        _run(functools.partial(ssd.ssd_chunked_xla, chunk=128), c)[0])


def test_compute_dtype_and_phase_count():
    """bfloat16 operands: the output is bfloat16, the state float32, and
    every traced call counts once as `ssd.core.xla`."""
    from ray_tpu.util import tracing

    c = _case(5, S=32, state=False)
    before = tracing.phase_table().get("ssd.core.xla", {}).get("count", 0)
    y, s = ssd.ssd_chunked(c["x"].astype(jnp.bfloat16), c["dt"], c["A"],
                           c["B"].astype(jnp.bfloat16),
                           c["C"].astype(jnp.bfloat16), c["D"], chunk=16)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    y_r, _ = _run(ssd.ssd_recurrent, c)
    assert float(jnp.abs(y.astype(jnp.float32) - y_r).max()) < 0.25
    assert tracing.phase_table()["ssd.core.xla"]["count"] == before + 1


def test_ssd_counts_by_hand():
    """reduce/ssd_counts.py at the cell's shape, by hand. A token, forward:
    C B^T (lower half, one group) 256 x 128 = 32,768; the intra-chunk
    product 64 heads x 64 x 256 = 1,048,576; a chunk's own state and
    Y_inter 2 x 64 x 64 x 2 x 128 = 2,097,152: 3,178,496. One layer at
    4,096 tokens, forward and backward: x 3 x 4096."""
    sys.path.insert(0, ROOT)
    from chipbench.reduce import ssd_counts

    f = ssd_counts.ssd_core_fwd_flops_per_token(64, 64, 128, 1, 256)
    assert f == 32_768 + 1_048_576 + 2_097_152 == 3_178_496
    cost = ssd_counts.ssd_core(1, 4096, 64, 64, 128, 1, 256)
    assert cost["flops"] == 3 * 4096 * 3_178_496 == 39_057_358_848
    # Bytes a token: x 8,192 (bf16), dt 256 (f32), B and C 512 (bf16) in,
    # y 8,192 out: forward 17,152; backward reads those and dy and writes a
    # gradient of each input: 17,152 + 8,960 = 26,112.
    assert cost["bytes"] == 4096 * (17_152 + 26_112) == 177_209_344
    # The program's own count agrees (models/transformer.py flops_per_token).
    from ray_tpu.models.transformer import TransformerConfig

    one = TransformerConfig(n_layers=1, mamba_layers=(1,))
    none = TransformerConfig(n_layers=1, mamba_layers=(1,), mamba_chunk=0,
                             mamba_d_state=0)
    extra = (one.flops_per_token(8) - 6 * one.num_params()
             ) - (none.flops_per_token(8) - 6 * none.num_params())
    assert extra == 3 * f
