"""The one backward kernel of ops/flash_attention.py (interpret mode on the
CPU): against the dQ and dK/dV kernels it replaced and against XLA's autodiff
of the plain attention, the rule that picks it from the shapes, what
`flash.plan` says of it, and the pair's jaxpr held to the parent's. A file of
its own: `--dist loadfile` gives a file to one worker, and these cases trace
three gradient programs each."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention, tile_sizes
from test_flash_attention import _BAND, _rand_qkv

_BF16 = jnp.bfloat16
# (S, H, KVH, D, Dv, causal, window, dtype, tiles): float32 with small named
# tiles reaches every body of the backward (interior strips, the diagonal's
# cells, a band's cells, whole masked tiles of a ragged or oblong grid);
# bfloat16 with nothing named lands on `_TILES`' four rows.
_FUSED = [
    (384, 2, 2, 64, 64, True, None, jnp.float32, _BAND),
    (1024, 2, 1, 32, 32, True, 512, jnp.float32,
     dict(block_q=256, block_k=256, sub=64)),
    (2048, 2, 1, 128, 128, True, 1024, _BF16, {}),   # a band inside a block
    (128, 8, 1, 64, 64, True, None, jnp.float32,
     dict(block_q=64, block_k=64, sub=32)),
    (128, 9, 1, 64, 64, True, 50, jnp.float32,
     dict(block_q=64, block_k=64, sub=32)),
    (300, 2, 1, 64, 64, True, None, jnp.float32, _BAND),
    (300, 2, 1, 64, 32, False, None, jnp.float32,
     dict(block_q=128, block_k=64, sub=32)),
    (300, 2, 1, 64, 32, False, None, jnp.float32,
     dict(block_q=64, block_k=128, sub=32)),
    (300, 2, 1, 32, 32, True, 70, jnp.float32, _BAND),
    (1024, 2, 2, 64, 64, True, None, _BF16, {}),
    (2048, 2, 1, 128, 128, True, None, _BF16, {}),
    (2048, 2, 2, 192, 128, True, None, _BF16, {}),
    (2048, 2, 1, 256, 256, True, None, _BF16, {}),
]


def _flash_grads(q, k, v, w, **kw):
    loss = lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **kw).astype(jnp.float32) * w)
    return jax.grad(loss, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize(
    "S,H,KVH,D,Dv,causal,window,dtype,tiles", _FUSED,
    ids=[f"S{c[0]}-H{c[1]}_{c[2]}-D{c[3]}_{c[4]}-"
         f"{'causal' if c[5] else 'full'}-W{c[6]}-"
         f"{'bf16' if c[7] == _BF16 else 'f32'}-"
         f"{c[8].get('block_q')}_{c[8].get('block_k')}" for c in _FUSED])
def test_fused_backward_against_the_pair_and_autodiff(
        monkeypatch, S, H, KVH, D, Dv, causal, window, dtype, tiles):
    """The one backward kernel (dK, dV and dQ from one S, dP and
    exponential) against the dQ and dK/dV kernels it replaces, which a
    budget of nothing still selects, and against XLA's autodiff of the
    plain attention: full causal, windows of 512 and 1,024, 8 and 9 query
    heads a key head, a ragged last block on either side, the table's four
    head widths."""
    q, k, v = _rand_qkv(jax.random.key(S + D), 1, S, H, KVH, D, dtype, Dv)
    w = jax.random.normal(jax.random.key(3), (1, S, H, Dv))
    kw = dict(causal=causal, window=window, **tiles)
    assert fa.bwd_kind(S, D, Dv, dtype, **tiles) == "fused"
    fused = _flash_grads(q, k, v, w, **kw)
    monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", 0)
    assert fa.bwd_kind(S, D, Dv, dtype, **tiles) == "split"
    pair = _flash_grads(q, k, v, w, **kw)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
        q, k, v, causal=causal, window=window) * w), (0, 1, 2))(*f32)
    # Against the pair only the order of dQ's float32 sums differs: one
    # rounding of the result in bfloat16. Against autodiff as the shapes'
    # test: P and dS are rounded to 2^-8 before their products.
    near, far = (2.0 ** -7, 2e-2) if dtype == _BF16 else (1e-5, 1e-4)
    for name, a, b, c in zip(("dq", "dk", "dv"), fused, pair, want):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        top = max(float(np.max(np.abs(np.asarray(c)))), 1.0)
        assert a.shape == c.shape and np.isfinite(a).all(), name
        assert np.max(np.abs(a - b)) <= near * top, (name, "pair")
        assert np.max(np.abs(a - np.asarray(c))) <= far * top, (name, "ref")


@pytest.mark.parametrize("S,D,Dv,dtype,kind", [
    (16384, 192, 128, _BF16, "fused"),   # kanana: 16 + 16 MiB
    (16384, 256, 256, _BF16, "fused"),
    (16384, 128, 128, _BF16, "fused"),
    (1024, 64, 64, _BF16, "fused"),
    (32768, 192, 128, _BF16, "fused"),   # 8 M elements in whole lanes:
    (65536, 128, 128, _BF16, "fused"),   # the budget to the byte
    (65536, 192, 128, _BF16, "split"),
    (131072, 128, 128, _BF16, "split"),
    (16384, 256, 256, jnp.float32, "fused"),
    (32768, 256, 256, jnp.float32, "split"),
])
def test_backward_kind_follows_the_heads_bytes(S, D, Dv, dtype, kind):
    """Fused where a head's float32 dQ accumulator and dQ's two output
    buffers fit `FUSED_DQ_VMEM_BUDGET` (D in whole lanes of 128), the pair
    beyond it; from the shapes alone."""
    assert fa.bwd_kind(S, D, Dv, dtype) == kind
    bq = tile_sizes(S, D, Dv, dtype)[0]
    fits = fa._dq_head_bytes(S, bq, D, jnp.dtype(dtype).itemsize) <= (
        fa.FUSED_DQ_VMEM_BUDGET)
    assert fits == (kind == "fused")


@pytest.mark.parametrize("budget,kind,calls", [(None, "fused", 2),
                                               (1 << 16, "split", 3)])
def test_flash_plan_says_which_backward(monkeypatch, budget, kind, calls):
    """The forward's `flash.plan` observation carries `bwd`, the traced
    backward counts itself as `flash.plan.bwd_<kind>`, and the gradient's
    program holds that many kernel calls: a head past the budget (here a
    budget made small) takes the pair and says so."""
    from ray_tpu.util import tracing

    if budget is not None:
        monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", budget)
    seen = []
    observe = tracing.observe
    monkeypatch.setattr(tracing, "observe", lambda name, *a, **kw: (
        seen.append((name, kw)), observe(name, *a, **kw))[1])
    q, k, v = _rand_qkv(jax.random.key(7), 1, 256, 2, 2, 32)
    tiles = dict(block_q=128, block_k=128, sub=64)
    jaxpr = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, **tiles)), (0, 1, 2)))(q, k, v)
    plans = [kw for name, kw in seen if name == "flash.plan"]
    assert [p["bwd"] for p in plans] == [kind]
    assert [n for n, _ in seen if n.startswith("flash.plan.bwd")] == [
        f"flash.plan.bwd_{kind}"]
    assert str(jaxpr).count("pallas_call") == calls
    assert f"flash.plan.bwd_{kind}" in tracing.phase_table()


@pytest.mark.parametrize("S,H,KVH,D,Dv,causal,window,dtype,tiles,digest", [
    (256, 2, 1, 64, 64, True, None, jnp.float32, _BAND, "6bdfad91cc66"),
    (300, 2, 1, 64, 32, False, None, jnp.float32,
     dict(block_q=128, block_k=64, sub=32), "cacd8dd52a30"),
    (512, 2, 1, 32, 32, True, 100, jnp.float32, _BAND, "a7cae943f607"),
    (2048, 2, 2, 192, 128, True, None, _BF16, {}, "e4da156a9c38"),
    (2048, 2, 1, 128, 128, True, 1024, _BF16, {}, "daf2d25493a3"),
])
def test_the_pair_traces_what_it_traced(monkeypatch, S, H, KVH, D, Dv, causal,
                                        window, dtype, tiles, digest):
    """Past the budget a gradient's jaxpr (forward, dQ and dK/dV kernels,
    bodies included) is the one PR 46's tree traced, by sha256 prefix: the
    fused backward's parts are traced only where it is taken. After a
    deliberate change to the forward or the pair, print the new ones."""
    import hashlib

    monkeypatch.setattr(fa, "FUSED_DQ_VMEM_BUDGET", 0)
    q, k, v = (jnp.zeros((1, S, h, d), dtype)
               for h, d in ((H, D), (KVH, D), (KVH, Dv)))
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=causal, window=window, **tiles
                        ).astype(jnp.float32)), (0, 1, 2)))(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:12] == digest
