"""A recurrent mixer's way from a projection to its core (ops/kda.py
`mixer_conv`): the Pallas pair in interpret mode against the XLA body the
mixers wrote out before it (`short_conv`, SiLU, `l2_normalize`), the dispatch
rule as a pure function, the observation a traced call makes, and the three
mixers through the kernels against themselves through the XLA body. Every
call runs under `jax.jit` (ROADMAP D11). The kernels compiled for a v5e at
the cells' shapes are in tests/test_kda_kernel_compile.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, transformer as tfm
from ray_tpu.ops import kda
from ray_tpu.util import tracing

pytestmark = pytest.mark.usefixtures("exact_matmuls")


@pytest.fixture()
def small_blocks(monkeypatch):
    """Blocks of 32 (forward) and 16 (backward) rows by 128 lanes, so that a
    sequence of 40 rows over 256 channels is several row and column blocks
    with a last one that is not whole. (A block is at least `_HALO` = 16
    rows and K - 1 <= 8, so no block boundary falls inside a sequence's
    first K - 1 rows; the sequence shorter than its taps is the case
    below.)"""
    forget = lambda: [f.clear_cache() for f in (kda._conv_fwd_call,
                                                kda._conv_bwd_call)]
    monkeypatch.setattr(kda, "_CONV_ROWS", (32, 16))
    monkeypatch.setattr(kda, "_CONV_COLS", (128,))
    forget()
    yield
    forget()


def _operands(B, S, ch, K, bias, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = jax.random.normal(ks[0], (B, S) + ch, dtype)
    w = jax.random.normal(ks[1], (K,) + ch) * 0.5
    b = jax.random.normal(ks[2], ch) * 0.1 if bias else None
    return x, w, b, jax.random.normal(ks[3], (B, S) + ch)


def _value_and_grads(body, l2, wo):
    def loss(x, w, b):
        return jnp.sum(body(x, w, b, l2=l2).astype(jnp.float32) * wo)
    return jax.jit(lambda x, w, b: (
        body(x, w, b, l2=l2), jax.grad(loss, argnums=(0, 1, 2))(x, w, b)))


@pytest.mark.parametrize("K,l2,bias,dtype", [
    (2, True, False, jnp.float32), (4, True, True, jnp.bfloat16),
    (4, False, False, jnp.bfloat16), (2, False, True, jnp.float32),
    (4, True, False, jnp.float32), (2, True, True, jnp.bfloat16),
    (4, False, True, jnp.float32), (2, False, False, jnp.bfloat16)])
def test_kernels_agree_with_the_xla_body(small_blocks, K, l2, bias, dtype):
    """y, dx, dw, db of the pair against the XLA body on float32 operands:
    two sequences of 40 rows (a forward block and a quarter, two backward
    blocks and a half), two heads of 128 channels (two column blocks), taps
    2 and 4, the norm and the bias on and off. float32 operands agree to
    float32 rounding; bfloat16 ones to one rounding of the result, where the
    XLA body on the same operands rounds several times."""
    x, w, b, wo = _operands(2, 40, (2, 128), K, bias, dtype)
    ref = _value_and_grads(kda.mixer_conv_xla, l2, wo)(
        x.astype(jnp.float32), w, b)
    got = _value_and_grads(kda.mixer_conv_pallas, l2, wo)(x, w, b)
    assert got[0].dtype == dtype and got[1][0].dtype == dtype
    assert got[1][1].dtype == w.dtype
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    for name, a, r in zip(("y", "dx", "dw", "db"), (got[0],) + got[1],
                          (ref[0],) + ref[1]):
        if r is None:
            assert a is None and name == "db" and not bias
            continue
        np.testing.assert_allclose(
            a.astype(jnp.float32), r, err_msg=name,
            atol=tol * float(jnp.abs(r).max()))


def test_a_sequence_shorter_than_its_taps():
    """Three rows under four taps at the blocks the cells run (one block,
    not whole): the rows before the sequence are zeros, the rows after it
    reach no gradient."""
    x, w, b, wo = _operands(1, 3, (128,), 4, True, jnp.float32)
    ref = _value_and_grads(kda.mixer_conv_xla, False, wo)(x, w, b)
    got = _value_and_grads(kda.mixer_conv_pallas, False, wo)(x, w, b)
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, r, atol=2e-5 * float(jnp.abs(r).max()))


@pytest.mark.parametrize("platform,channels,group,on_mesh,kernels", [
    ("cpu", 4096, 128, False, False), ("tpu", 64, None, False, False),
    ("tpu", 4096, 64, False, False), ("tpu", 4096, 128, True, False),
    ("tpu", 4096, 128, False, True), ("tpu", 256, None, False, True)])
def test_the_dispatch_rule(platform, channels, group, on_mesh, kernels):
    """A pure function of what the code observes: the kernels on a TPU,
    whole 128-lane tiles, the norm over groups of 128 or none, no mesh."""
    assert kda.use_conv_kernels(platform, channels, group,
                                on_mesh) is kernels


def test_a_traced_call_counts_itself():
    """On the CPU the dispatcher takes the XLA body and says so once a
    traced call, with what it observed."""
    x, w, b, _ = _operands(1, 8, (2, 128), 4, True, jnp.float32)
    count = lambda: [tracing.phase_table().get(
        "mixer.conv." + body, {}).get("count", 0) for body in ("xla", "pallas")]
    xla, pallas = count()  # (another file of this process may have counted)
    y = jax.jit(lambda x, w, b: kda.mixer_conv(x, w, b, l2=True))(x, w, b)
    assert count() == [xla + 1, pallas]
    np.testing.assert_allclose(
        y, kda.l2_normalize(jax.nn.silu(kda.short_conv(x, w) + b)),
        atol=1e-6)


def _mixer_cfg(mixer):
    kw = dict(n_layers=1, d_model=128, d_ff=128, max_seq_len=64,
              moe_num_experts=0, moe_held=None, moe_shared_experts=0,
              dtype=jnp.float32)
    if mixer == "gdn":
        return configs.qwen3_next_tiny(gdn_head_dim=128, gdn_chunk=8,
                                       gdn_k_heads=1, gdn_v_heads=2,
                                       moe_shared_gate=False, **kw)
    if mixer == "kda":
        return configs.kimi_linear_tiny(kda_head_dim=128, kda_chunk=8,
                                        kda_heads=2, **kw)
    kw = {k: v for k, v in kw.items() if not k.startswith("moe_")}
    return configs.granite_hybrid_tiny(mamba_heads=8, mamba_head_dim=16,
                                       mamba_d_state=64, **kw)


@pytest.mark.parametrize("mixer,sites", [("gdn", 3), ("kda", 3),
                                         ("mamba2", 2)])
def test_the_mixers_through_the_kernels(monkeypatch, mixer, sites):
    """One layer of each recurrent mixer, output and parameter gradients,
    with the convolution path through the kernels (the rule told it is on a
    TPU; the cores stay XLA's) against the same layer through the XLA body,
    at tests/test_kda.py's and test_ssd_kernels.py's tolerance. The kernels
    count `sites` call sites a layer: q, k, v of a delta-rule layer (the
    joint leaves' halves are projected apart), x and B | C of a Mamba-2
    one."""
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

    count = lambda n: tracing.phase_table().get(n, {}).get("count", 0)
    (_, y_xla), g_xla = run()
    rule = kda.use_conv_kernels
    monkeypatch.setattr(kda, "use_conv_kernels",
                        lambda _, *a: rule("tpu", *a))
    before = count("mixer.conv.pallas"), count("mixer.conv.xla")
    (_, y), g = run()
    assert count("mixer.conv.pallas") - before[0] == sites
    assert count("mixer.conv.xla") == before[1]
    np.testing.assert_allclose(y, y_xla,
                               atol=2e-5 * float(jnp.abs(y_xla).max()))
    for a, r in zip(jax.tree.leaves(g), jax.tree.leaves(g_xla)):
        np.testing.assert_allclose(
            a, r, atol=1e-5 + 1e-4 * float(jnp.abs(r).max()))
