"""What the chip bring-up (PR 21) can check without a chip: who may touch
the device, where compiled programs go, and that nothing falls back to the
host quietly. The on-chip half is chip_smoke.py."""
import inspect
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ spawn env


def _env(**kw):
    from ray_tpu.core.worker_env import worker_env

    base = dict(controller="127.0.0.1:1", node_id="n", spawn_token="t",
                tpu_chips=None, node_chips=4)
    base.update(kw)
    return worker_env(**base)


def test_tpu_worker_never_defaults_to_cpu(monkeypatch):
    """A worker spawned for a TPU request gets no JAX_PLATFORMS=cpu, set or
    inherited; a plain worker always does and never inherits a chip grant."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
    tpu = _env(tpu_chips=[2])
    assert tpu["JAX_PLATFORMS"] == "tpu" and tpu["RTPU_TPU_WORKER"] == "1"
    plain = _env()
    assert plain["JAX_PLATFORMS"] == "cpu"
    assert "RTPU_TPU_WORKER" not in plain and "TPU_VISIBLE_CHIPS" not in plain
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert _env()["JAX_PLATFORMS"] == "cpu"


def test_sub_host_grant_declares_its_shape(monkeypatch):
    """A grant smaller than the host names its chips and their bounds; a
    whole-host grant leaves the machine's own TPU_* description alone."""
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_HOST_BOUNDS", "1,1,1")
    one = _env(tpu_chips=[3])
    assert (one["TPU_VISIBLE_CHIPS"], one["TPU_CHIPS_PER_HOST_BOUNDS"],
            one["TPU_HOST_BOUNDS"]) == ("3", "1,1,1", "1,1,1")
    two = _env(tpu_chips=[0, 1])
    assert (two["TPU_VISIBLE_CHIPS"],
            two["TPU_CHIPS_PER_HOST_BOUNDS"]) == ("0,1", "1,2,1")
    whole = _env(tpu_chips=[0, 1, 2, 3])
    assert "TPU_VISIBLE_CHIPS" not in whole
    assert whole["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"


def test_grant_chips_never_partial():
    from ray_tpu.core.worker_env import grant_chips

    free = [0, 1, 2]
    assert grant_chips(free, 2) == [0, 1] and free == [2]
    assert grant_chips(free, 2) == [] and free == [2]


def test_both_spawners_build_their_env_with_the_helper():
    from ray_tpu.core.controller import Controller
    from ray_tpu.core.host_agent import HostAgent

    for fn in (Controller._maybe_spawn_worker, HostAgent._spawn_worker):
        src = inspect.getsource(fn)
        assert "worker_env.worker_env(" in src
        assert "JAX_PLATFORMS" not in src and "child_env" not in src


# -------------------------------------------------------- compile cache


def _cache_dir_in_child(cwd, env):
    code = ("from ray_tpu.util.jaxenv import enable_compile_cache as e; "
            "import os; print(e()); "
            "print(os.environ['JAX_COMPILATION_CACHE_DIR'])")
    out = subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                         capture_output=True, text=True, check=True)
    return out.stdout.split()


def test_compile_cache_dir_is_fixed_in_the_checkout(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = ROOT
    want = os.path.join(ROOT, ".jax_cache")
    assert _cache_dir_in_child(ROOT, env) == [want, want]
    assert _cache_dir_in_child(str(tmp_path), env) == [want, want]


def test_compile_cache_dir_from_outside_wins(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, it is used as given and no cache
    path is set in code (only the thresholds are)."""
    from ray_tpu.util import jaxenv

    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    for name in jaxenv._CACHE_SETTINGS:
        monkeypatch.setenv(name.upper(), "unset")
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    assert jaxenv.enable_compile_cache() == placed
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == placed
    assert updates == jaxenv._CACHE_SETTINGS


# ------------------------------------------------------ no quiet fallback


def test_platform_probes_propagate_backend_errors(monkeypatch):
    from ray_tpu.ops import dispatch

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", broken)
    with pytest.raises(RuntimeError, match="backend 'tpu'"):
        dispatch.interpret()
    with pytest.raises(RuntimeError, match="backend 'tpu'"):
        dispatch.site()


def test_peak_flops_has_no_default_for_an_unknown_device():
    from ray_tpu.util.accelerators import peak_flops_per_chip

    assert peak_flops_per_chip("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        peak_flops_per_chip("TPU v9 imaginary")
    with pytest.raises(ValueError, match="cpu"):
        peak_flops_per_chip()  # the live device here is a cpu


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_chip_programs_refuse_to_run_off_chip(script):
    """Off the chip both exit non-zero, say why, and print no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(ROOT, script)],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no chip" in out.stderr or "no TPU" in out.stderr


# ----------------------------------------------------------- warm start


def test_engine_warmup_compiles_every_program_a_request_reaches():
    from ray_tpu.models import transformer as tfm
    from ray_tpu.models.configs import llama_tiny
    from ray_tpu.serve.llm_engine import ContinuousBatchingEngine

    cfg = llama_tiny(remat=False)
    params = tfm.init_params(jax.random.key(0), cfg)
    eng = ContinuousBatchingEngine(cfg, params, num_slots=2,
                                   max_prompt_len=16, max_new_tokens=4)
    eng.warmup()
    programs = (eng._prefill_one, eng._splice, eng._tick)
    sizes = [p._cache_size() for p in programs]
    assert sizes == [2, 1, 1]  # buckets 8 and 16
    a = eng.submit([5, 9, 2])
    b = eng.submit(list(range(1, 13)))
    while eng.tick():
        pass
    assert len(eng.result(a, timeout=60)) == 4
    assert len(eng.result(b, timeout=60)) == 4
    assert [p._cache_size() for p in programs] == sizes
