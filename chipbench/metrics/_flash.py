"""Shared by flash_fwd_roofline and flash_bwd_roofline: the kernel events of
the trace against the least time the chip could take for their shapes."""
import re

from chipbench.reduce import flash_counts


def roofline_pct(ctx, pattern: str, cost_fn):
    tr = ctx.get("trace") or {}
    names = [k for k in tr.get("op_self_s", {}) if re.search(pattern, k)]
    if not names:
        return None
    sz, st = ctx["sizes"], ctx["stats"]
    shards = ctx["cell"]["chips"]  # batch x heads are split over the mesh
    cost = cost_fn(st["batch"], sz.H, sz.KVH, st["seq"], sz.hd)
    cost = {k: v / shards for k, v in cost.items()}
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls = sum(tr["op_count"][k] for k in names)
    secs = sum(tr["op_self_s"][k] for k in names)
    ctx.setdefault("notes", {})[pattern] = {"bound": bound, "calls": calls}
    return calls, secs, least_s
