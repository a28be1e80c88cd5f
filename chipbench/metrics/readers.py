"""The general readers behind the per-layer metrics that are data files.

`chipbench/metrics/<name>.json` names a reader and its parameters; a metric
that needs code is `chipbench/metrics/<name>.py` with `read(ctx)`. Either
returns None when what it reads is not there, and the runner then leaves the
metric out of the line.

ctx (built by run.py): `phases` (seconds), `counts`, `series` (raw lists of
seconds from the client's clocks and the engine), `stats`, `trace` (the
reduced profiler trace, reduce/xplane.py), `cell`, `mix`, `sizes`, `peaks`,
`e2e`."""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Callable, Dict, Optional

from chipbench import common


def phase(ctx, key: str, **_):
    return ctx["phases"].get(key)


def count(ctx, key: str, **_):
    return ctx["counts"].get(key)


def stat(ctx, key: str, **_):
    return ctx["stats"].get(key)


def series_pct(ctx, series: str, q: float, scale: float = 1e3, **_):
    v = common.pct(ctx["series"].get(series) or [], q)
    return None if v is None else v * scale


def series_mean(ctx, series: str, scale: float = 1e3, **_):
    v = common.mean(ctx["series"].get(series) or [])
    return None if v is None else v * scale


def series_pct_diff(ctx, series: str, minus: str, q: float,
                    scale: float = 1e3, **_):
    a = common.pct(ctx["series"].get(series) or [], q)
    b = common.pct(ctx["series"].get(minus) or [], q)
    return None if a is None or b is None else (a - b) * scale


def trace_module_mean_ms(ctx, pattern: str, **_):
    """Mean device time of the compiled programs whose name matches."""
    tr = ctx.get("trace") or {}
    ms = [d for name, ds in tr.get("module_ms", {}).items()
          if re.search(pattern, name) for d in ds]
    return common.mean(ms)


def trace_op_share_pct(ctx, pattern: str, **_):
    """Share of the device's busy time spent in ops whose name matches."""
    tr = ctx.get("trace") or {}
    if not tr.get("busy_s"):
        return None
    s = sum(v for k, v in tr["op_self_s"].items() if re.search(pattern, k))
    return 100.0 * s / tr["busy_s"]


def trace_idle_pct(ctx, **_):
    tr = ctx.get("trace") or {}
    if not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


READERS: Dict[str, Callable] = {f.__name__: f for f in (
    phase, count, stat, series_pct, series_mean, series_pct_diff,
    trace_module_mean_ms, trace_op_share_pct, trace_idle_pct)}


def read(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    base = os.path.join(common.HERE, "metrics", name)
    if os.path.exists(base + ".py"):
        spec = importlib.util.spec_from_file_location(
            "chipbench.metrics._m_" + re.sub(r"\W", "_", name), base + ".py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)
    with open(base + ".json") as f:
        spec = json.load(f)
    return READERS[spec["reader"]](ctx, **spec.get("args", {}))
