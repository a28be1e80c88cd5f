"""What the presets with a held range of experts repeat on the program alone,
on the CPU: the train step with its routing counters under the policy each
cell runs, and the model through the flash kernels (interpret mode) against
the XLA path. One case a family (tests/test_expert_shares.py `FAMILIES`);
what only one family has is in that family's file."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, transformer as tfm
from ray_tpu.ops import moe
from test_expert_shares import DENSE_FAMILIES, family

pytestmark = pytest.mark.usefixtures("exact_matmuls")

# (preset, remat policy) -> overrides of the preset, the mesh's data axis,
# tokens [B, S + 1], counters held to a number, and whether the step is also
# compiled ahead of time.
STEPS = {
    ("kimi_linear_tiny", "full"): dict(
        over=dict(n_layers=2, moe_held=(8, 8)), data=2, toks=(4, 33),
        aot=True),
    ("mellum2_tiny", "dots"): dict(
        toks=(4, 65), exact=dict(moe_trips=4, moe_past_buffer=0,
                                 moe_window_rows=512)),
    ("kanana2_tiny", "full"): dict(toks=(4, 65)),
    ("qwen3_next_tiny", "dots"): dict(toks=(4, 65)),
    ("qwen3_next_tiny", "full"): dict(toks=(4, 65)),
    ("laguna_tiny", "dots"): dict(toks=(4, 65)),
}


@pytest.mark.parametrize("preset,policy", sorted(STEPS))
def test_train_step_returns_the_counters_and_folds_them(preset, policy):
    """transformer_train_step(with_counters=True) on the tiny preset under
    the remat policy (the kernels interpreted where they are reached): the
    step returns the routing counters beside the loss, the loss falls,
    nothing is dropped, the expert layers' assignments are the held share of
    tokens x k within 0.6 .. 1.4, a layer's window is `held_window_rows` of
    its sizes, only a routing past it takes a further trip, the row passes
    work whole blocks as far as the held rows reach, and `observe_counters`
    folds them into the phase table. mellum2's held half takes every
    assignment in its first window (2.5 of its even share is them all)."""
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.train.step import transformer_train_step
    from ray_tpu.util import tracing

    case = STEPS[preset, policy]
    cfg = getattr(configs, preset)(remat=True, remat_policy=policy,
                                   **case.get("over", {}))
    n = case.get("data", 1)
    mesh = make_mesh(MeshSpec(data=n), devices=jax.devices()[:n])
    ts = transformer_train_step(cfg, mesh, shift_inputs=True,
                                with_counters=True)
    params, opt = ts.init(jax.random.key(0))
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab_size, case["toks"]).astype(np.int32)
    before = tracing.phase_table().get("train.moe_assigned", {"count": 0})
    losses = []
    for _ in range(3):
        params, opt, loss, aux = ts.step(params, opt,
                                         ts.shard_batch({"tokens": toks}))
        losses.append(float(loss))
        seen = ts.observe_counters(aux)
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert seen["moe_dropped"] == 0.0
    tokens = case["toks"][0] * (case["toks"][1] - 1)
    layers = sum(ffn == "moe" for _, ffn in cfg.layer_kinds())
    k, E, held = (cfg.moe_experts_per_token, cfg.moe_num_experts,
                  cfg.moe_held_range[1])
    even = layers * tokens * k * held / E
    assert 0.6 * even < seen["moe_assigned"] < 1.4 * even
    assert seen["moe_load_max"] >= seen["moe_load_mean"] > 0
    assert seen["moe_window_rows"] == moe.held_window_rows(tokens, k, E, held)
    assert (seen["moe_trips"] > layers) == (seen["moe_past_buffer"] > 0)
    for name, want in case.get("exact", {}).items():
        assert seen[name] == want, name
    table = tracing.phase_table()
    assert table["train.moe_assigned"]["count"] == before["count"] + 3
    assert {"train.moe_trips", "train.moe_window_rows",
            "train.moe_rows_worked"} <= set(table)
    block = moe.block_rows(int(seen["moe_window_rows"]))
    assert seen["moe_rows_worked"] % block == 0 and (
        seen["moe_assigned"] <= seen["moe_rows_worked"]
        < seen["moe_assigned"] + seen["moe_trips"] * block)
    if case.get("aot"):  # one executable for the loop and memory_analysis()
        batch = ts.shard_batch({"tokens": toks})
        exe = ts.compile_step(params, opt, batch)
        assert exe.memory_analysis().temp_size_in_bytes > 0
        params, opt, loss, aux = ts.step(params, opt, batch)
        assert float(loss) < losses[-1] and ts._compiled_step is exe


# The leaves of the benchmark's comparison the flash path is held to: a
# windowed layer's and a full one's; the latent layer's two projections.
FLASH_LEAVES = {"mellum2_tiny": ("swa_wkv", "full_wq"),
                "kanana2_tiny": ("mla_wq", "mla_wkva"),
                "laguna_tiny": ("swa_wkv", "swa_gate", "full_wq"),
                "ouro_tiny": ("wq_first", "wo_last")}
def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("preset", sorted(FLASH_LEAVES))
def test_the_flash_path_is_the_xla_path(preset, request):
    """The model through the flash kernels (interpret mode here: windowed
    and full layers at heads of 16; keys 24 wide and values 16), under both
    remat policies, on the family's seeded weights: the loss and the
    gradients of the family's attention leaves are the XLA path's. The
    looped stack's 4 | 4 heads of 16 make eight calls a step, every pass's
    residuals kept under "full"."""
    W, _, sizes = family(preset)
    cfg = getattr(configs, preset)(dtype=jnp.float32)
    sz = sizes(cfg)
    params = W.program_params(jax.random.key(21), sz, cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(22), (2, 49), 0,
                                          cfg.vocab_size)}
    grad = lambda cfg: jax.value_and_grad(lambda p: tfm.loss_fn(
        p, batch, cfg, shift_inputs=True))
    want_loss, g = jax.jit(grad(cfg))(params)
    want = W.program_leaves(cfg, sz, g)
    request.getfixturevalue("flash_kernels")  # from here on
    # ROADMAP D11: the looped stack's eight interpreted calls a policy are
    # 15 s each; its cell runs "full", and "dots" differs in no kernel call
    for policy in ("full",) if preset in DENSE_FAMILIES else ("dots", "full"):
        remat = dataclasses.replace(cfg, remat=True, remat_policy=policy)
        loss, g = jax.jit(grad(remat))(params)
        assert abs(float(loss) - float(want_loss)) < 1e-5, policy
        got = W.program_leaves(remat, sz, g)
        for leaf in FLASH_LEAVES[preset]:
            assert _rel(got[leaf], want[leaf]) < 2e-5, (policy, leaf)
