"""A held range of experts (ops/moe.py `moe_ffn_held`) against a plain
masked loop over the held experts, at every held share and however the
routing falls on the range. A file of its own: these eighteen cases are
half of tests/test_moe.py's seconds, and `--dist loadfile` gives a file to
one worker."""
import jax
import numpy as np
import pytest

from test_moe import _assert_close, _held_case, _held_loop, _loss_and_grads


@pytest.mark.parametrize("kind", ["sigmoid", "softmax"])
@pytest.mark.parametrize("routing", ["even", "all_held", "none_held"])
@pytest.mark.parametrize("share", [1, 4, 32])
def test_held_layer_equals_a_masked_loop(share, routing, kind):
    """Output and the gradients of x, the router and both weight stacks of
    `moe_ffn_held` are those of a plain masked loop over the held experts,
    at every held share and however the routing falls on the held range:
    the window follows the held share, the loop takes the trips the held
    assignments need and not one more, nothing is dropped. With every
    expert held the one window is every assignment."""
    import functools

    import jax.numpy as jnp

    from ray_tpu.ops import moe

    args, wy, route, first = _held_case(share, routing, kind)
    T, k, E, Eh = 256, 2, 64, 64 // share
    with jax.default_matmul_precision("highest"):
        y, cnt, grads = _loss_and_grads(functools.partial(
            moe.moe_ffn_held, route=route, held_first=first,
            dtype=jnp.float32), args, wy)
        want = _loss_and_grads(
            lambda *a: (_held_loop(*a, route=route, first=first), {}),
            args, wy)
    _assert_close(y, want[0], "output")
    for name, g, w in zip(("x", "router", "gate_up", "down"), grads, want[2]):
        _assert_close(g, w, name)
    rows = moe.held_window_rows(T, k, E, Eh)
    more = moe.further_window_rows(rows)  # half of it, to 128 rows
    held = int(cnt["assigned"])
    assert float(cnt["window_rows"]) == rows
    assert float(cnt["trips"]) == 1 + min(max(-(-(held - rows) // more), 0),
                                          -(-(T * k - rows) // more))
    assert float(cnt["dropped"]) == 0.0
    assert float(cnt["past_buffer"]) == max(held - rows, 0)
    if share == 1:
        assert rows == T * k == held and float(cnt["trips"]) == 1.0
    else:
        assert rows < T * k  # the window follows the held share
        if routing == "all_held":
            assert held == T * k and float(cnt["trips"]) > 1.0
        if routing == "none_held":
            assert held == 0 and not np.any(np.asarray(y))
