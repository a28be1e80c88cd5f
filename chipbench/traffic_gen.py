"""The one general traffic generator: a mix file's parameters -> the
requests and arrival times of one run.

Every seed gets the same multiset of request sizes and of gaps between
arrivals (drawn once from the mix's `base_seed`) in another order, and its
own token ids: the seed shuffles the work, it does not change how much
there is. The gaps of an open loop are scaled to fill exactly the ramp plus
the window, so every run offers the same number of requests.

Copied in spirit from nothing: benchmarks/serve_bench.py floods with
fixed-length prompts and has no arrival process."""
from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def _lengths(rng: np.random.Generator, spec: Dict[str, Any], n: int):
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec.get("min", 1),
                   spec.get("max", 1 << 30)).astype(int)


def population(mix: Dict[str, Any], n: int):
    """n (prompt_len, output_len) pairs, the same for every seed."""
    rng = np.random.default_rng(mix["base_seed"])
    return list(zip(_lengths(rng, mix["prompt_len"], n).tolist(),
                    _lengths(rng, mix["output_len"], n).tolist()))


def _prompt(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, n).tolist()


def open_loop(mix: Dict[str, Any], seed: int, seconds: float, vocab: int):
    """Poisson arrivals at mix['rate_rps'] from -ramp_s to `seconds`:
    [{"due": s relative to the window's start, "tokens", "max_new_tokens"}]."""
    span = mix["ramp_s"] + seconds
    n = max(1, round(mix["rate_rps"] * span))
    base = np.random.default_rng(mix["base_seed"] + 1)
    gaps = base.exponential(1.0, n)
    gaps *= span / gaps.sum()
    pairs = population(mix, n)
    rng = np.random.default_rng(seed)
    gaps = gaps[rng.permutation(n)]
    order = rng.permutation(n)
    due = np.cumsum(gaps) - gaps[0] * 0.5 - mix["ramp_s"]
    return [{"due": float(due[i]), "max_new_tokens": pairs[j][1],
             "tokens": _prompt(rng, pairs[j][0], vocab)}
            for i, j in enumerate(order)]


def closed_loop(mix: Dict[str, Any], seed: int, vocab: int):
    """The cyclic list the clients of a closed loop draw from, in turn."""
    pairs = population(mix, mix["population"])
    rng = np.random.default_rng(seed)
    return [{"max_new_tokens": pairs[j][1],
             "tokens": _prompt(rng, pairs[j][0], vocab)}
            for j in rng.permutation(len(pairs))]


def train_tokens(mix: Dict[str, Any], seed: int, vocab: int) -> np.ndarray:
    """[pool, batch, seq + 1] token ids a training run cycles through."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (mix["pool"], mix["batch"], mix["seq"] + 1),
                        dtype=np.int32)
