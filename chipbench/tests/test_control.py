"""The control of `correct`, at a size a test run can hold (CPU, the tiny
presets): the comparison that the benchmark runs must fail for a run one
precision step below the one the configuration states.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

On the chip at the cells' own sizes the same functions are read by
chipbench/limits.py; the readings and the limits are in PERF.md section 2."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import common, inworker as iw  # noqa: E402


@pytest.mark.parametrize("config", ["gpt2_124m", "internlm2_1_8b"])
def test_train_control_fails_where_the_program_passes(config):
    import jax

    from chipbench import weights
    from chipbench.reference import dense_decoder as ref
    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel import MeshSpec, make_mesh

    cfgd = common.load_json("configs", config + ".json")
    limit = cfgd["limits"]["train_grad_rel_err"]
    sz = iw.sizes(cfgd, True)
    cfg = iw.transformer_config(cfgd, True, remat=True, remat_policy="dots")
    mesh = make_mesh(MeshSpec(), devices=jax.devices()[:1])
    params = jax.jit(lambda k: weights.program_params(k, sz))(
        jax.random.key(11))
    loss_fn = lambda p, b: tfm.loss_fn(p, b, cfg, shift_inputs=True)
    sound = iw.train_check(loss_fn, params, mesh, sz, 11, 2, 64)
    control = iw.train_control(sz, 11, 2, 64, ref.mm_fp8)
    assert sound["train_grad_rel_err"] <= limit, sound
    assert control["train_grad_rel_err"] > limit, control
    assert control["train_grad_rel_err"] > 3 * sound["train_grad_rel_err"]


def test_reference_in_bfloat16_agrees_with_the_program_not_with_fp8():
    """The reference with bfloat16 matmuls (the precision the configuration
    states) stays inside the limit; with fp8 operands it does not."""
    from chipbench.reference import dense_decoder as ref

    cfgd = common.load_json("configs", "internlm2_1_8b.json")
    limit = cfgd["limits"]["train_grad_rel_err"]
    sz = iw.sizes(cfgd, True)
    assert iw.train_control(sz, 5, 2, 64, ref.mm_bf16)[
        "train_grad_rel_err"] <= limit
    assert iw.train_control(sz, 5, 2, 64, ref.mm_fp8)[
        "train_grad_rel_err"] > limit
