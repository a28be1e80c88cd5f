"""A recurrent mixer's way from its core to its output projection (ops/kda.py
`gated_norm`): the Pallas pair in interpret mode against the XLA body the
three mixers wrote out before it, the dispatch rule as a pure function, the
observation a traced call makes, and the three mixers through the kernels
against themselves through the XLA body. Every call runs under `jax.jit`
(ROADMAP D11). The kernels compiled for a v5e at the cells' shapes are in
tests/test_kda_kernel_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.ops import kda
from ray_tpu.util import tracing
from test_mixer_conv import _mixer_cfg

pytestmark = pytest.mark.usefixtures("exact_matmuls")

# What each layer kind IS: (gate_act, gate_first), the group by the shape.
FORMS = {"gdn": ("silu", False), "kda": ("sigmoid", False),
         "mamba2": ("silu", True)}


def _forget():
    for f in (kda._norm_fwd_call, kda._norm_bwd_call):
        f.clear_cache()


@pytest.fixture()
def small_blocks(monkeypatch):
    """Blocks of 32 (forward) and 16 (backward) rows, lanes in parts of 128:
    a sequence of 40 rows is several row blocks with a last one that is not
    whole, 256 channels are two column blocks where the group is 128 and one
    group of two parts where it is all of them."""
    monkeypatch.setattr(kda, "_CONV_ROWS", (32, 16))
    monkeypatch.setattr(kda, "_CONV_COLS", (128,))
    _forget()
    yield
    _forget()


def _operands(B, S, ch, group, dtype, seed=0):
    """y, gate [B, S, ...ch] in `dtype`, a float32 weight a head ([group],
    where a head is a group) or a channel, and the loss's weights."""
    ks = jax.random.split(jax.random.key(seed), 4)
    y = jax.random.normal(ks[0], (B, S) + ch, dtype)
    gate = jax.random.normal(ks[1], (B, S) + ch, dtype) * 2.0
    w = 1.0 + 0.5 * jax.random.normal(ks[2], ch[-1:] if ch[-1] == group
                                      else ch)
    return y, gate, w, jax.random.normal(ks[3], (B, S) + ch)


def _value_and_grads(body, wo, **kw):
    def loss(y, gate, w):
        return jnp.sum(body(y, gate, w, **kw).astype(jnp.float32) * wo)
    return jax.jit(lambda y, gate, w: (
        body(y, gate, w, **kw), jax.grad(loss, argnums=(0, 1, 2))(y, gate,
                                                                  w)))


def _agree(form, ch, group, dtype, B=2, S=40):
    act, first = FORMS[form]
    kw = dict(group=group, gate_act=act, gate_first=first, eps=1e-5)
    y, gate, w, wo = _operands(B, S, ch, group, dtype)
    ref = _value_and_grads(kda.gated_norm_xla, wo, **kw)(
        y.astype(jnp.float32), gate.astype(jnp.float32), w)
    got = _value_and_grads(kda.gated_norm_pallas, wo, **kw)(y, gate, w)
    assert got[0].dtype == dtype and got[0].shape == y.shape
    assert [g.dtype for g in got[1]] == [dtype, dtype, w.dtype]
    assert got[1][2].shape == w.shape
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    for name, a, r in zip(("out", "dy", "dgate", "dw"), (got[0],) + got[1],
                          (ref[0],) + ref[1]):
        np.testing.assert_allclose(
            a.astype(jnp.float32), r, err_msg=name,
            atol=tol * float(jnp.abs(r).max()))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("form,ch,group", [
    ("gdn", (2, 128), 128), ("kda", (2, 128), 128),
    ("mamba2", (4, 64), 256), ("mamba2", (2, 128), 128)])
def test_kernels_agree_with_the_xla_body(small_blocks, form, ch, group,
                                         dtype):
    """out, dy, dgate, dw of the pair against the XLA body on float32
    operands: two sequences of 40 rows (a ragged S: a forward block and a
    quarter, two backward blocks and a half), a norm a head of 128 with one
    weight the heads share (dw folded over them) and a norm over all 256
    channels with a weight a channel (one group of two parts: the two-pass
    body), the gate after the norm as SiLU and as a sigmoid and before it.
    float32 operands agree to float32 rounding; bfloat16 ones to one
    rounding of the result."""
    _agree(form, ch, group, dtype)


@pytest.mark.parametrize("form,ch,group,S", [
    ("gdn", (4, 128), 128, 24), ("kda", (1, 128), 128, 3),
    ("mamba2", (8, 64), 512, 24), ("mamba2", (16, 64), 1024, 16),
    ("gdn", (3, 256), 256, 24)])
def test_the_cells_blocks(form, ch, group, S):
    """At the blocks the cells run (one row block, not whole): a part of 512
    lanes that holds four groups of 128, one group of 128 alone and three
    rows, a part that is the group, a group of two parts, and three groups
    of 256 (a block a group, since no wider one divides 768 channels)."""
    _forget()
    _agree(form, ch, group, jnp.float32, B=1, S=S)


@pytest.mark.parametrize("platform,channels,group,on_mesh,kernels", [
    ("cpu", 4096, 128, False, False), ("tpu", 4096, 128, True, False),
    ("tpu", 4096, 64, False, False), ("tpu", 4032, 4032, False, False),
    ("tpu", 4096, 384, False, False), ("tpu", 4096, 128, False, True),
    ("tpu", 4096, 4096, False, True), ("tpu", 768, 256, False, True)])
def test_the_dispatch_rule(platform, channels, group, on_mesh, kernels):
    """A pure function of what the code observes: the kernels on a TPU under
    no mesh, channels of whole 128-lane tiles, a group of whole tiles that
    divides them."""
    assert kda.use_norm_kernels(platform, channels, group,
                                on_mesh) is kernels


@pytest.mark.parametrize("form", sorted(FORMS))
def test_a_traced_call_counts_itself(form):
    """On the CPU the dispatcher takes the XLA body and says so once a
    traced call, with what it observed; the body is the expression the
    form's mixer wrote out."""
    act, first = FORMS[form]
    y, gate, w, _ = _operands(1, 8, (2, 128), 128, jnp.float32)
    count = lambda: [tracing.phase_table().get(
        "mixer.gated_norm." + body, {}).get("count", 0)
                     for body in ("xla", "pallas")]
    xla, pallas = count()
    out = jax.jit(lambda y, gate, w: kda.gated_norm(
        y, gate, w, group=128, gate_act=act, gate_first=first, eps=None))(
            y, gate, w)
    assert count() == [xla + 1, pallas]
    a = (jax.nn.silu if act == "silu" else jax.nn.sigmoid)(gate)
    norm = lambda x: x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(out, norm(y * a) if first else norm(y) * a,
                               atol=1e-5)


@pytest.mark.parametrize("mixer", sorted(FORMS))
def test_the_mixers_through_the_kernels(monkeypatch, mixer):
    """One layer of each recurrent mixer, output and parameter gradients,
    with the gated norm through the kernels (the rule told it is on a TPU;
    the cores and the convolutions stay XLA's) against the same layer through
    the XLA body. The kernels count one call site a layer, with the form its
    layer kind is."""
    cfg = _mixer_cfg(mixer)
    kind = cfg.layer_kinds()[0]
    assert kind[0] == mixer
    B, S = 1, 24
    layer = tfm.layer_params(tfm.init_params(jax.random.key(0), cfg), cfg, 0)
    x = jax.random.normal(jax.random.key(1), (B, S, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def run():  # a new function each time: jax caches a trace by identity
        def loss(x, layer):
            y = tfm.layer_scan_body(cfg, kind, positions)(x, layer)[0]
            return jnp.sum(y * jnp.cos(y)), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(x, layer)

    row = lambda n: tracing.phase_table().get(n, {})
    count = lambda n: row(n).get("count", 0)
    (_, y_xla), g_xla = run()
    rule = kda.use_norm_kernels
    monkeypatch.setattr(kda, "use_norm_kernels",
                        lambda _, *a: rule("tpu", *a))
    _forget()
    before = count("mixer.gated_norm.pallas"), count("mixer.gated_norm.xla")
    (_, y), g = run()
    assert count("mixer.gated_norm.pallas") - before[0] == 1
    assert count("mixer.gated_norm.xla") == before[1]
    np.testing.assert_allclose(y, y_xla,
                               atol=2e-5 * float(jnp.abs(y_xla).max()))
    for a, r in zip(jax.tree.leaves(g), jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(
            a, r, atol=1e-5 + 1e-4 * float(jnp.abs(r).max()))
