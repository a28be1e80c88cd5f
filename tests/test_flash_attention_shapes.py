"""The flash kernels (interpret mode on the CPU) against the reference at
the shapes `_TILES` serves, forward and all three gradients: bfloat16 at real
sizes with the tiles the table gives, float32 with small named tiles. A
file of its own: these eighteen cases are two thirds of
tests/test_flash_attention.py's seconds, and `--dist loadfile` gives a file
to one worker."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention
from test_flash_attention import _rand_qkv

# (S, D, Dv, H, KVH, causal, dtype, tiles). bfloat16 with no tiles named
# lands on `_TILES`' rows at real sizes (S under a block, a block, ragged,
# several blocks; two heads where every head has its own keys, four over two
# key heads where they are shared: a head is a grid step of its own); float32 with small named tiles checks every body tightly:
# strips on a diagonal tile, interior strips, the masked whole tile of a
# ragged or oblong grid, a strip that divides nothing.
_BF16, _F32 = jnp.bfloat16, jnp.float32
_CASES = [
    (384, 64, 64, 2, 2, True, _BF16, {}),
    (384, 128, 128, 4, 2, False, _BF16, {}),
    (1024, 64, 64, 2, 2, True, _BF16, {}),
    (1024, 64, 64, 4, 2, False, _BF16, {}),
    (1024, 192, 128, 2, 2, True, _BF16, {}),
    (1280, 64, 64, 4, 2, True, _BF16, {}),
    (1280, 128, 128, 2, 2, True, _BF16, {}),
    (1280, 192, 128, 2, 2, False, _BF16, {}),
    (2048, 64, 64, 2, 2, True, _BF16, {}),
    (2048, 128, 128, 4, 2, True, _BF16, {}),
    (2048, 128, 128, 2, 2, False, _BF16, {}),
    (2048, 192, 128, 4, 2, True, _BF16, {}),
    (256, 64, 64, 4, 2, True, _F32, dict(block_q=128, block_k=128, sub=32)),
    (256, 192, 128, 2, 2, True, _F32, dict(block_q=256, block_k=256, sub=64)),
    (256, 64, 64, 2, 1, False, _F32, dict(block_q=128, block_k=128, sub=32)),
    (256, 32, 32, 2, 2, True, _F32, dict(block_q=64, block_k=128, sub=32)),
    (320, 64, 64, 2, 1, True, _F32, dict(block_q=128, block_k=128, sub=64)),
    (192, 64, 64, 2, 2, True, _F32, dict(block_q=96, block_k=96, sub=64)),
]


@pytest.mark.parametrize(
    "S,D,Dv,H,KVH,causal,dtype,tiles", _CASES,
    ids=[f"S{c[0]}-D{c[1]}_{c[2]}-H{c[3]}_{c[4]}-{'causal' if c[5] else 'full'}"
         f"-{'bf16' if c[6] == _BF16 else 'f32'}" for c in _CASES])
def test_forward_and_gradients_match_reference(S, D, Dv, H, KVH, causal,
                                               dtype, tiles):
    q, k, v = _rand_qkv(jax.random.key(S + D), 1, S, H, KVH, D, dtype, Dv)
    f32 = lambda xs: [x.astype(jnp.float32) for x in xs]

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal, **tiles)
    ref = lambda q, k, v: reference_attention(q, k, v, causal=causal)
    # (each a program of its own, jitted: op by op the wrappers' small ops
    # compile one at a time, a quarter of the case, ROADMAP D11)
    grad = lambda fn: jax.jit(jax.grad(loss(fn), (0, 1, 2)))
    out, grads = jax.jit(flash)(q, k, v), grad(flash)(q, k, v)
    want = jax.jit(ref)(*f32((q, k, v)))
    wgrads = grad(ref)(*f32((q, k, v)))
    assert out.shape == (1, S, H, Dv) and out.dtype == dtype
    # bfloat16: P and the outputs are rounded to 2^-8, so a few 2^-8 of the
    # largest value (benchmarks/probe_flash.py TOL); a wrong mask is O(1).
    tol = 2e-2 if dtype == _BF16 else 1e-4
    for name, a, b in zip(("o", "dq", "dk", "dv"), [out, *grads],
                          [want, *wgrads]):
        b = np.asarray(b)
        err = np.max(np.abs(np.asarray(a.astype(jnp.float32)) - b))
        assert err <= tol * np.max(np.abs(b)), (name, err, np.max(np.abs(b)))
