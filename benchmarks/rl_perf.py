"""RLlib sampling/training throughput (BASELINE.json config 4 proxy).

Three metrics, one JSON line each (also written to benchmarks/RL_PERF.json):

1. cnn_sample_steps_per_s — fragment sampler + Nature-CNN policy on the
   synthetic Atari-shaped CnnRolloutBenchEnv ([84,84,4] uint8, whole batch
   steps in numpy). Measures the sampler + batched-inference architecture
   (the reference's vectorized env runner path,
   rllib/env/single_agent_env_runner.py:701); it is NOT a real game.
   Runs the policy on the TPU when one is visible (batched device
   inference), else CPU.
2. ppo_sample_steps_per_s — fragment sampling on real gymnasium CartPole.
3. ppo_train_steps_per_s — full PPO iterations (sample -> vectorized GAE
   -> learner minibatch SGD -> weight broadcast).

Run from the repo root: python benchmarks/rl_perf.py
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json
import time


def bench_cnn_sampler(device: str, num_envs=256, T=32, reps=3) -> dict:
    import jax

    from ray_tpu.rllib.core.catalog import CNNModule
    from ray_tpu.rllib.env.env_runner import SingleAgentEnvRunner
    from ray_tpu.rllib.env.vector_env import CnnRolloutBenchEnv

    def make_batched(n):
        return CnnRolloutBenchEnv(n)

    make_batched.makes_batched_env = True

    runner = SingleAgentEnvRunner(
        make_batched, lambda: CNNModule((84, 84, 4), 6),
        num_envs=num_envs, seed=0, device=device)
    runner.set_weights(runner.module.init(jax.random.key(0)))
    runner.sample_fragment(4)  # warm compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        runner.sample_fragment(T)
        best = min(best, time.perf_counter() - t0)
    steps = T * num_envs
    return {"metric": "cnn_sample_steps_per_s",
            "value": round(steps / best, 1), "unit": "env-steps/s",
            "num_envs": num_envs, "fragment_len": T,
            # report what jax ACTUALLY initialized, not the request —
            # a host without a TPU silently falls back to CPU.
            "policy_device": jax.devices()[0].platform,
            "note": "synthetic Atari-shaped batched env (framework+inference "
                    "ceiling; not a real game)"}


def main(iters=6, warmup=2):
    # CNN sampler runs in a SUBPROCESS: it may initialize the TPU backend,
    # and once jax has a backend the parent's CPU pin below would silently
    # no-op — the PPO numbers must stay CPU-measured and reproducible.
    import subprocess

    use_tpu = not os.environ.get("JAX_PLATFORMS", "").startswith("cpu")
    child = (
        "import sys, json; sys.path.insert(0, {root!r});"
        "sys.path.insert(0, {here!r});"
        "from rl_perf import bench_cnn_sampler;"
        "print(json.dumps(bench_cnn_sampler({dev!r})))"
    ).format(root=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
             here=os.path.dirname(os.path.abspath(__file__)),
             dev="tpu" if use_tpu else "cpu")
    out = {"metric": "cnn_sample_steps_per_s", "value": 0.0,
           "error": "subprocess failed"}
    try:
        p = subprocess.run([sys.executable, "-c", child], capture_output=True,
                           text=True, timeout=1200)
        for line in reversed(p.stdout.splitlines()):
            if line.startswith("{"):
                out = json.loads(line)
                break
        else:
            out["error"] = (p.stderr or "no output").strip()[-200:]
    except subprocess.TimeoutExpired:
        out["error"] = "timeout"
    print(json.dumps(out), flush=True)

    from ray_tpu.util.jaxenv import ensure_platform

    # The driver's learner/GAE stays on the host: a chip, if there is one,
    # belonged to the sampler child above (one process per chip).
    ensure_platform("cpu")

    import ray_tpu
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    ray_tpu.init(num_cpus=4)
    config = (
        PPOConfig()
        .environment("CartPole-v1")
        .env_runners(num_env_runners=2, num_envs_per_env_runner=8,
                     rollout_fragment_length=128)
        .training(train_batch_size=2048, minibatch_size=512,
                  num_epochs=4, lr=3e-4)
    )
    algo = config.build()

    # Pure fragment-sampling rate (actors sample concurrently).
    group = algo.env_runner_group
    group.sync_weights(algo.learner_group.get_weights())
    group.sample_fragments(8)  # warm compiles
    t0 = time.perf_counter()
    n = 0
    for _ in range(4):
        frags = group.sample_fragments(128)
        n += sum(int(f["valid"].sum()) for f in frags)
    dt = time.perf_counter() - t0
    print(json.dumps({"metric": "ppo_sample_steps_per_s",
                      "value": round(n / dt, 1), "unit": "env-steps/s"}),
          flush=True)

    for _ in range(warmup):
        algo.train()
    t0 = time.perf_counter()
    steps = 0
    for _ in range(iters):
        result = algo.train()
        steps += result["env_steps_this_iter"]
    dt = time.perf_counter() - t0
    print(json.dumps({"metric": "ppo_train_steps_per_s",
                      "value": round(steps / dt, 1), "unit": "env-steps/s",
                      "iters": iters}), flush=True)
    algo.stop()

    # Vectorized-env PPO: the envpool-style path — env state is ONE array
    # batch (env/vector_env.py CartPoleBatchedEnv, ~1.6M raw steps/s on
    # this host vs ~10k for per-env Python), policy inference is one
    # batched forward per vector step, fragments feed vectorized GAE.
    # This is the configuration the reference's 1M env-steps/s numbers
    # come from (envpool + GPU inference), so it's the honest shape for
    # the env-steps/s north star.
    from ray_tpu.rllib.env.vector_env import CartPoleBatchedEnv

    def batched_cartpole(num_envs):
        return CartPoleBatchedEnv(num_envs, seed=17)

    batched_cartpole.makes_batched_env = True

    config = (
        PPOConfig()
        .environment(env_creator=batched_cartpole)
        .env_runners(num_env_runners=2, num_envs_per_env_runner=256,
                     rollout_fragment_length=32)
        .training(train_batch_size=16384, minibatch_size=4096,
                  num_epochs=2, lr=3e-4)
    )
    algo = config.build()
    for _ in range(warmup):
        algo.train()
    t0 = time.perf_counter()
    steps = 0
    for _ in range(iters):
        result = algo.train()
        steps += result["env_steps_this_iter"]
    dt = time.perf_counter() - t0
    print(json.dumps({"metric": "ppo_train_batched_steps_per_s",
                      "value": round(steps / dt, 1), "unit": "env-steps/s",
                      "iters": iters,
                      "num_envs": 512}), flush=True)
    algo.stop()
    ray_tpu.shutdown()


if __name__ == "__main__":
    import io, os, contextlib

    buf = io.StringIO()

    class Tee(io.TextIOBase):
        def __init__(self, *sinks): self.sinks = sinks
        def write(self, t):
            for s_ in self.sinks: s_.write(t)
            return len(t)
        def flush(self):
            for s_ in self.sinks: s_.flush()

    import sys as _sys
    with contextlib.redirect_stdout(Tee(_sys.stdout, buf)):
        main()
    out = {}
    for line in buf.getvalue().splitlines():
        try:
            r = json.loads(line)
            out[r["metric"]] = r
        except Exception:
            pass
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "RL_PERF.json"), "w") as f:
        json.dump(out, f, indent=1)
