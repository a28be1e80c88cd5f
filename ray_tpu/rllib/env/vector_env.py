"""Batched environment layer for high-throughput sampling.

Parity: reference rllib/env/single_agent_env_runner.py:701 builds
gym.vector envs (sync or async/subprocess); the reference's 1M env-steps/s
IMPALA numbers rest on many vectorized envs per runner with ONE policy
forward per vector step. This module defines the batched-env protocol the
fragment sampler (env_runner.sample_fragment) consumes and three backends:

- GymVecEnv: gymnasium sync/async vector envs (NEXT_STEP autoreset — the
  step after a done returns the reset observation and ignores its action,
  which the sampler records as an invalid row).
- CnnRolloutBenchEnv: a pure-numpy Atari-shaped synthetic env whose whole
  batch steps in a few vector ops (SAME_STEP autoreset). It exists to
  measure the sampler+policy-inference ceiling without ALE in the image;
  it is NOT a real game (benchmarks/rl_perf.py labels it as overhead probe).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import numpy as np


class BatchedEnv:
    """Protocol: step the WHOLE batch with arrays, no per-env Python.

    autoreset_mode:
    - "next_step": gymnasium semantics — done step returns the FINAL
      observation; the following step ignores its action and returns the
      reset observation (an invalid transition the sampler masks).
    - "same_step": done step returns the final reward/flags but the
      returned observation is already the reset observation of the next
      episode (no invalid rows; truncation bootstrap unavailable — only
      suitable for termination-only envs).
    """

    num_envs: int
    autoreset_mode: str = "next_step"
    single_observation_space: Any = None
    single_action_space: Any = None

    def reset(self, seed: Optional[int] = None) -> np.ndarray:
        raise NotImplementedError

    def step(self, actions: np.ndarray
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """-> (obs [N,...], rewards [N] f32, terminations [N] bool,
        truncations [N] bool)"""
        raise NotImplementedError

    def close(self) -> None:
        pass


class GymVecEnv(BatchedEnv):
    """gymnasium vector env adapter; mode="sync" (one process) or
    "async" (subprocess per env — reference's remote envs / envpool idea
    for CPU-heavy env steps)."""

    def __init__(self, env_creator: Callable[[], Any], num_envs: int,
                 mode: str = "sync"):
        import gymnasium as gym

        self.num_envs = num_envs
        if mode == "async":
            self.envs = gym.vector.AsyncVectorEnv(
                [env_creator for _ in range(num_envs)])
        elif mode == "sync":
            self.envs = gym.vector.SyncVectorEnv(
                [env_creator for _ in range(num_envs)])
        else:
            raise ValueError(
                f"unknown vectorize mode {mode!r} (want 'sync' or 'async')")
        self.autoreset_mode = "next_step"
        self.single_observation_space = self.envs.single_observation_space
        self.single_action_space = self.envs.single_action_space

    def reset(self, seed: Optional[int] = None) -> np.ndarray:
        obs, _ = self.envs.reset(seed=seed)
        return obs

    def step(self, actions):
        obs, rew, term, trunc, _ = self.envs.step(actions)
        return obs, np.asarray(rew, np.float32), term, trunc

    def close(self) -> None:
        self.envs.close()


class CnnRolloutBenchEnv(BatchedEnv):
    """Atari-shaped throughput probe: [84, 84, 4] uint8 observations drawn
    from a pre-generated bank, reward = f(action), geometric episode ends.
    The entire batch steps in O(3) numpy ops — what remains in the profile
    is the sampler's own overhead plus policy inference."""

    autoreset_mode = "same_step"

    def __init__(self, num_envs: int, obs_shape=(84, 84, 4),
                 num_actions: int = 6, mean_len: int = 1000, seed: int = 0):
        import gymnasium as gym

        self.num_envs = num_envs
        self.obs_shape = tuple(obs_shape)
        self._rng = np.random.default_rng(seed)
        # 64-frame bank; each env walks it at its own stride.
        self._bank = self._rng.integers(
            0, 255, (64, *self.obs_shape), dtype=np.uint8)
        self._pos = self._rng.integers(0, 64, num_envs)
        self._stride = 1 + self._rng.integers(0, 3, num_envs)
        self._p_done = 1.0 / float(mean_len)
        self.single_observation_space = gym.spaces.Box(
            0, 255, self.obs_shape, np.uint8)
        self.single_action_space = gym.spaces.Discrete(num_actions)

    def reset(self, seed: Optional[int] = None) -> np.ndarray:
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._pos = self._rng.integers(0, 64, self.num_envs)
        return self._bank[self._pos % 64]

    def step(self, actions):
        self._pos = self._pos + self._stride
        obs = self._bank[self._pos % 64]
        rew = (np.asarray(actions) % 3).astype(np.float32) * 0.1
        term = self._rng.random(self.num_envs) < self._p_done
        # SAME_STEP autoreset: obs is already the next episode's start for
        # done envs (the bank walk just continues).
        trunc = np.zeros(self.num_envs, bool)
        return obs, rew, term, trunc


class CartPoleBatchedEnv(BatchedEnv):
    """Vectorized CartPole-v1: the WHOLE batch integrates in ~6 numpy ops.

    Same dynamics, reward and termination thresholds as gymnasium's
    CartPole-v1 (Euler integration, tau=0.02, 500-step truncation) — but
    no per-env Python objects, so a single core steps hundreds of
    thousands of env-steps/s instead of ~10k. This is the envpool-style
    answer the reference reaches for at its 1M env-steps/s scale: the env
    batch is array state, policy inference is one batched forward, and
    nothing in the sampling loop is O(num_envs) Python.

    SAME_STEP autoreset: terminated/truncated columns return the reset
    observation immediately (CartPole is termination-heavy; the masked
    invalid rows of NEXT_STEP would waste ~1/200 of throughput)."""

    autoreset_mode = "same_step"

    GRAVITY, MASSCART, MASSPOLE = 9.8, 1.0, 0.1
    LENGTH, FORCE_MAG, TAU = 0.5, 10.0, 0.02
    THETA_LIMIT = 12 * 2 * np.pi / 360
    X_LIMIT = 2.4
    MAX_STEPS = 500

    def __init__(self, num_envs: int, seed: int = 0):
        import gymnasium as gym

        self.num_envs = num_envs
        self._rng = np.random.default_rng(seed)
        self._state = np.zeros((num_envs, 4), np.float64)
        self._t = np.zeros(num_envs, np.int64)
        self.single_observation_space = gym.spaces.Box(
            -np.inf, np.inf, (4,), np.float32)
        self.single_action_space = gym.spaces.Discrete(2)

    def _reset_rows(self, rows: np.ndarray) -> None:
        n = int(rows.sum()) if rows.dtype == bool else len(rows)
        if n:
            self._state[rows] = self._rng.uniform(-0.05, 0.05, (n, 4))
            self._t[rows] = 0

    def reset(self, seed: Optional[int] = None) -> np.ndarray:
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        self._reset_rows(np.ones(self.num_envs, bool))
        return self._state.astype(np.float32)

    def step(self, actions):
        x, x_dot, theta, theta_dot = self._state.T
        force = np.where(np.asarray(actions) == 1, self.FORCE_MAG,
                         -self.FORCE_MAG)
        cos, sin = np.cos(theta), np.sin(theta)
        total_mass = self.MASSCART + self.MASSPOLE
        polemass_length = self.MASSPOLE * self.LENGTH
        temp = (force + polemass_length * theta_dot**2 * sin) / total_mass
        theta_acc = (self.GRAVITY * sin - cos * temp) / (
            self.LENGTH * (4.0 / 3.0 - self.MASSPOLE * cos**2 / total_mass))
        x_acc = temp - polemass_length * theta_acc * cos / total_mass
        x = x + self.TAU * x_dot
        x_dot = x_dot + self.TAU * x_acc
        theta = theta + self.TAU * theta_dot
        theta_dot = theta_dot + self.TAU * theta_acc
        self._state = np.stack([x, x_dot, theta, theta_dot], axis=1)
        self._t += 1

        term = (np.abs(x) > self.X_LIMIT) | (np.abs(theta) > self.THETA_LIMIT)
        trunc = (self._t >= self.MAX_STEPS) & ~term
        rew = np.ones(self.num_envs, np.float32)
        done = term | trunc
        if done.any():
            self._reset_rows(done)  # SAME_STEP: fresh obs ride this return
        return self._state.astype(np.float32), rew, term, trunc
