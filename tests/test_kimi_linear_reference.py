"""The program against the plain reference (chipbench/reference/
kimi_linear.py), weights from one seed: each kind of layer alone, a
five-layer stack under remat, and the published 27-layer pattern at tiny
widths. A file of its own: these five cases are most of the stack tests'
seconds, and `--dist loadfile` gives a file to one worker."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.models.configs import kimi_linear_tiny

pytestmark = pytest.mark.usefixtures("exact_matmuls")


def _reference_parts(cfg, seed=5):
    from chipbench import weights_kimi_linear as WK

    tc = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sz = WK.HybridSizes(tc, cfg.norm_eps)
    key = jax.random.key(seed)
    params = jax.jit(lambda k: WK.program_params(k, sz, cfg))(key)
    return sz, key, params


@pytest.mark.parametrize("name,over", [
    ("kda_dense", dict(n_layers=1)),
    ("kda_moe", dict(n_layers=1, moe_first_dense=0)),
    ("mla_moe", dict(n_layers=1, moe_first_dense=0, kda_layers=(),
                     mla_layers=(1,))),
    ("stack5_remat", dict(remat=True, remat_policy="dots")),
    ("pattern27", dict(n_layers=27)),
])
def test_program_matches_reference(name, over):
    """Logits, loss and the compared gradient leaves, program against the
    plain reference, weights from one seed: each layer kind alone and the
    published 27-layer pattern (irregular tail included) at tiny widths."""
    from chipbench.drivers import train_hybrid as drv
    from chipbench.reference import kimi_linear as ref

    cfg = kimi_linear_tiny(dtype=jnp.float32, moe_held=(4, 4), **over)
    sz, key, params = _reference_parts(cfg)
    if cfg.n_layers == 27:
        kinds = [p for p, _ in cfg.stack_plan()]
        assert [len(p) for p in kinds] == [1, 4, 1, 1], cfg.stack_plan()
        assert cfg.stack_plan()[1][1] == 6
    deep = cfg.n_layers == 27  # the reference unrolls: keep its compile short
    toks = jax.random.randint(jax.random.key(1), (1, 17) if deep else (2, 41),
                              0, cfg.vocab_size)
    if not deep:
        np.testing.assert_allclose(
            jax.jit(lambda p: tfm.forward(p, toks[:, :-1], cfg))(params),
            ref.forward(key, toks[:, :-1], sz), atol=2e-4)
    loss_p, g = jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
        p, {"tokens": toks}, cfg, shift_inputs=True)))(params)
    # Eagerly when deep: 27 unrolled layers compile as one program for
    # minutes, op by op the layers share their compiled pieces.
    ref_grads = lambda k, t: ref.loss_and_grads(k, t, sz)
    loss_r, g_r = (ref_grads if deep else jax.jit(ref_grads))(key, toks)
    assert abs(float(loss_p) - float(loss_r)) < 1e-5 * float(loss_r)
    # Leaves of layer kinds this stack lacks are absent from the program.
    has = {m for m, _ in sz.kinds} | {f for _, f in sz.kinds}
    want = {"final_norm": True, "kda_wo": "kda" in has,
            "mla_wkvb": "mla" in has, "expert_down": "moe" in has,
            "router": "moe" in has}
    lay = lambda l: tfm.layer_params(g, cfg, l)
    got = {"final_norm": g["final_norm"]}
    if want["kda_wo"]:
        got["kda_wo"] = lay(sz.l_kda)["kda_wo"].reshape(-1, sz.d)
    if want["mla_wkvb"]:
        got["mla_wkvb"] = lay(sz.l_mla)["mla_wkvb"].reshape(sz.lat, -1)
    if want["expert_down"]:
        got["expert_down"] = lay(sz.l_moe)["moe_w_down"][sz.e_pick]
        got["router"] = lay(sz.l_moe)["router"]
    for n, a in got.items():
        err = float(jnp.linalg.norm(a - g_r[n]) / jnp.linalg.norm(g_r[n]))
        assert err < 2e-4, (name, n, err)
    if cfg.n_layers == 27:
        assert set(drv.program_leaves(cfg, sz, g)) == set(g_r)
