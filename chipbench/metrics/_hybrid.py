"""Shared by the per-layer metrics of the hybrid stack's train cell: the
configuration's sizes, device time by scope, and the steps in the traced
window."""
import os
import re

from chipbench.reduce import flash_counts, scopes


def sizes(ctx):
    """HybridSizes of the cell's configuration, None for a dense one."""
    from chipbench.weights_kimi_linear import sizes_of

    config = ctx["cell"]["config"]
    if "kda_layers" not in config["transformer_config"]:
        return None
    return sizes_of(config,
                    bool(int(os.environ.get("CHIPBENCH_REHEARSE", "0"))))


def scope_share_pct(ctx, names):
    pic = scopes.picture(ctx)
    if not pic.get("busy_s"):
        return None
    found = [pic["scope_s"][n] for n in names if n in pic["scope_s"]]
    return 100.0 * sum(found) / pic["busy_s"] if found else None


def steps_traced(ctx):
    """Step programs' worth of device work in the traced window: busy time
    over the mean device time of one whole step."""
    tr = ctx.get("trace") or {}
    ms = [d for name, ds in tr.get("module_ms", {}).items()
          if re.search("^jit__step$", name) for d in ds]
    if not ms or not tr.get("busy_s"):
        return None
    return tr["busy_s"] / (sum(ms) / len(ms) / 1e3)


def flash_roofline_pct(ctx, pattern, cost_fn):
    """As metrics/_flash.py, with the latent attention's two widths and one
    call a softmax layer of the stack."""
    tr, sz = ctx.get("trace") or {}, sizes(ctx)
    names = [k for k in tr.get("op_self_s", {}) if re.search(pattern, k)]
    if not names or sz is None:
        return None
    st = ctx["stats"]
    cost = cost_fn(st["batch"], sz.H, st["seq"], sz.nope + sz.rope, sz.dv)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls = sum(tr["op_count"][k] for k in names)
    secs = sum(tr["op_self_s"][k] for k in names)
    ctx.setdefault("notes", {})["mla_" + pattern] = {"bound": bound,
                                                     "calls": calls}
    return 100.0 * least_s * calls / secs

