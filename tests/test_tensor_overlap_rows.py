"""The residual cut along the sequence over `tensor`
(parallel/tensor_overlap.py): the sharded step gives one device's loss and
gradients, on every mesh and under remat. A file of its own: these eight
cases are a third of tests/test_tensor_overlap.py's seconds, and `--dist
loadfile` gives a file to one worker late in the run."""
import jax
import numpy as np
import pytest

from test_tensor_overlap import (MESHES, MODELS, ONE, RULES_TP, _count,
                                 _loss_and_grads, _tokens)


@pytest.mark.parametrize("remat", [True, False], ids=["dots", "no_remat"])
@pytest.mark.parametrize("model,mesh", [
    ("llama", "fsdp2xtp2"), ("llama", "fsdp4xtp2"),
    ("llama", "dp2xfsdp2xtp2"), ("gpt2", "fsdp2xtp2")])
def test_sharded_rows_give_one_devices_loss_and_gradients(model, mesh, remat):
    cfg = MODELS[model](remat=remat, remat_policy="dots")
    tokens = _tokens(cfg)
    before = _count()
    loss, grads = _loss_and_grads(cfg, MESHES[mesh], RULES_TP, tokens)
    assert _count() == before + 1  # one layer body traced, and it engaged
    loss1, grads1 = _loss_and_grads(cfg, ONE, RULES_TP, tokens)
    assert _count() == before + 1  # and not on a mesh of one device
    assert abs(loss - loss1) < 1e-2, (loss, loss1)
    for (path, a), b in zip(jax.tree.leaves_with_path(grads1),
                            jax.tree.leaves(grads)):
        # bfloat16 activations: sums in another order round apart.
        scale = np.abs(a).max()
        np.testing.assert_allclose(b, a, atol=4e-2 * scale,
                                   err_msg=jax.tree_util.keystr(path))
        assert np.linalg.norm(a - b) <= 2e-2 * np.linalg.norm(a), (
            jax.tree_util.keystr(path))
