"""Shared by the per-layer metrics of a `stack` configuration with
learned-sparse-attention layers (configs/keye_vl_2_0_30b_a3b.json): device
time under the `dsa` scope (models/transformer.py `_dsa_mixer`) and the two
inside it, `dsa.index` (the indexer's projections, the scores and the exact
selection, the indexer's loss and its gradient) and `dsa.core` (attention
over the selection, forward and backward; ops/sparse_attention.py opens both
round its own calls too, because a backward rule is traced outside the
mixer), held to reduce/keye_vl2_counts.py at the sizes of the `stack`
section. Every reader returns None where no op carries a `dsa` scope (an
older program, another cell, an untraced run) or the configuration's counts
module has no such count."""
from chipbench.metrics import _routed
from chipbench.metrics._stack import sizes_and_counts, steps_traced
from chipbench.reduce import flash_counts, scopes

SCOPES = ("dsa.core", "dsa.index", "dsa")  # inner scopes first


def picture(ctx):
    """{"busy_s", "scope_s"} of the run's trace file, once a run
    (ctx["dsa"]); {} when the run was not traced or no op carries a scope of
    SCOPES (a program without the mixer)."""
    if "dsa" not in ctx:
        path, pic = _routed._trace_file(ctx), {}
        if path:
            pic = scopes.by_scope(scopes.load(path), SCOPES)
            if any(s in pic.get("scope_s", {}) for s in SCOPES):
                ctx.setdefault("notes", {})["dsa_scope_s"] = pic["scope_s"]
            else:
                pic = {}
        ctx["dsa"] = pic
    return ctx["dsa"]


def share_pct(ctx, names):
    """The named scopes' share of the busy time."""
    pic = picture(ctx)
    if not pic.get("busy_s"):
        return None
    return 100.0 * sum(pic["scope_s"].get(n, 0.0) for n in names
                       ) / pic["busy_s"]


def roofline_pct(ctx, scope, costs):
    """The device seconds a step spends under `scope` against `costs(counts,
    sz, stats)` (a list of {"flops", "bytes"}, a layer and a step): least
    seconds x layers x steps over the seconds."""
    (sz, counts), steps = sizes_and_counts(ctx), steps_traced(ctx)
    secs = picture(ctx).get("scope_s", {}).get(scope)
    if sz is None or not steps or not secs or not hasattr(counts, "dsa_index"):
        return None
    least, bounds = 0.0, []
    for cost in costs(counts, sz, ctx["stats"]):
        s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
        least, bounds = least + s, bounds + [bound]
    layers = sum(m == "dsa" for m, _ in sz.kinds)
    ctx.setdefault("notes", {})[scope] = {
        "bound": bounds, "layers": layers, "steps_traced": steps,
        "ms_a_step": 1e3 * secs / steps, "least_ms_a_layer": 1e3 * least}
    return 100.0 * least * layers * steps / secs
