"""Test fixtures (reference: python/ray/tests/conftest.py ray_start_regular:419,
ray_start_cluster:500).

JAX is forced onto a virtual 8-device CPU platform before any test imports it,
so sharding/collective tests run the real pjit/shard_map paths without TPU
hardware (SURVEY.md §4.4 test-ring 2).
"""
from ray_tpu.util.jaxenv import cpu_mesh_env

cpu_mesh_env(8)

import pytest  # noqa: E402


# ROADMAP D11: `--dist loadfile` hands a worker a whole file, and pytest-xdist
# (3.8) sends the files out by their number of cases, most first, so a file
# of five cases and 200 s starts last and is the run's tail (90 s of 1,300).
# The files of 60 case-seconds or more in the last whole run (`PERF.md`
# section 7) go out first instead, longest first; the rest follow as xdist
# orders them. A PR that moves a file across that line moves its name here.
LONGEST_FILES = (
    "test_kda_kernel_compile", "test_kimi_linear_reference", "test_laguna",
    "test_preset_programs", "test_dsa", "test_qwen3_next", "test_kda",
    "test_moe", "test_models", "test_expert_shares", "test_model_table",
    "test_flash_backward", "test_kimi_linear", "test_tensor_overlap",
    "test_kda_scalar", "test_granite_hybrid", "test_ssd_kernels",
    "test_kda_remat", "test_flash_attention", "test_flash_attention_shapes",
    "test_rollout", "test_serve", "test_llm_engine", "test_generate",
    "test_ouro", "test_tensor_overlap_rows", "test_moe_held_loop",
    "test_fused_ce", "test_kanana2", "test_replay_buffers",
    "test_serve_disagg", "test_rllib", "test_transfer_fastpath",
    "test_actors", "test_lfm2_moe", "test_sambay")


def pytest_collection_modifyitems(config, items):
    cases = {}
    for item in items:
        cases[item.path] = cases.get(item.path, 0) + 1
    rank = {name: i for i, name in enumerate(LONGEST_FILES)}
    items.sort(key=lambda item: (rank.get(item.path.stem, len(rank)),
                                 -cases[item.path]))


def pytest_configure(config):
    # (the scheduler keeps the order above: `--no-loadscope-reorder`)
    config.option.loadscopereorder = False
    config.addinivalue_line(
        "markers",
        "slow: long-running stress/chaos variants excluded from tier-1 "
        "(run with -m slow)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection tests (drain/preemption/kill harnesses). "
        "Fast chaos tests stay inside the tier-1 'not slow' set; stress "
        "variants are additionally marked slow.")


@pytest.fixture()
def flash_kernels(monkeypatch):
    """Flash's rule says yes on the platform here, the CPU: `attention()`
    takes the kernels, interpreted. A test steers the rule, as
    tests/test_kda_remat.py does KDA's; the program has no option for it."""
    from ray_tpu.ops import flash_attention

    monkeypatch.setattr(flash_attention, "use_kernels", lambda platform: True)


@pytest.fixture(scope="module")
def ray_start_regular():
    import ray_tpu

    handle = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield handle
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_cluster():
    from ray_tpu.core.cluster_utils import Cluster

    cluster = Cluster(head_resources={"CPU": 2})
    yield cluster
    cluster.shutdown()


@pytest.fixture()
def exact_matmuls():
    """float32 matmuls as float32 (the CPU's default rounds their inputs):
    for the files that hold a program to a reference within 1e-5."""
    import jax

    with jax.default_matmul_precision("highest"):
        yield
