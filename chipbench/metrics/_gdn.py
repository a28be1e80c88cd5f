"""Shared by the per-layer metrics of a `stack` configuration with Gated
DeltaNet layers and a gated attention layer (configs/qwen3_next_80b_a3b.json):
device time under the `gdn` scope and `gdn.core` inside it
(models/transformer.py `_gdn_mixer`; ops/kda.py opens `gdn.core` around its
kernels too, because the backward rule is traced outside the mixer), and the
three flash kernels' calls and seconds under the `gattn` scope
(`_routed.kernel_seconds`: a kernel named from its HLO text by
reduce/xplane.py, its mixer from its op's name stack by reduce/scopes.py),
held to reduce/qwen3_next_counts.py at the sizes of the `stack` section.
Every reader returns None where the `gdn` scope or the kernels are not in
the trace (an older program, another cell, an untraced run)."""
from chipbench.metrics import _routed
from chipbench.metrics._stack import sizes_and_counts, steps_traced
from chipbench.reduce import flash_counts, scopes, xplane

SCOPES = ("gdn.core", "gdn", "gattn.gate", "gattn")  # inner scopes first


def picture(ctx):
    """{"busy_s", "scope_s", "kernels"} of the run's trace file, once a run
    (ctx["gdn"]); {} when the run was not traced or no op carries the `gdn`
    scope (a program without the mixer)."""
    if "gdn" not in ctx:
        path, pic = _routed._trace_file(ctx), {}
        if path:
            events = scopes.load(path)
            pic = scopes.by_scope(events, SCOPES)
            if any(s in pic.get("scope_s", {}) for s in ("gdn.core", "gdn")):
                labels = {e[2]: e[5] for e in xplane.load(path)
                          if e[1] == "XLA Ops"}
                pic["kernels"] = _routed.kernel_seconds(
                    events, labels, scope="gattn")["in"]
                ctx.setdefault("notes", {})["gdn"] = {
                    k: pic[k] for k in ("scope_s", "kernels")}
            else:
                pic = {}
        ctx["gdn"] = pic
    return ctx["gdn"]


def share_pct(ctx, core: bool):
    """The `gdn` scope's share of the busy time with `gdn.core` inside it;
    without the core where `core` is false."""
    pic = picture(ctx)
    if not pic.get("busy_s"):
        return None
    secs = pic["scope_s"].get("gdn", 0.0)
    if core:
        secs += pic["scope_s"].get("gdn.core", 0.0)
    return 100.0 * secs / pic["busy_s"]


def core_roofline_pct(ctx):
    """The `gdn.core` scope's device seconds a step against the scalar-decay
    chunked rule's forward and backward of every DeltaNet layer (the remat
    re-run of the forward is in the measured time and not in the count)."""
    (sz, counts), steps = sizes_and_counts(ctx), steps_traced(ctx)
    secs = picture(ctx).get("scope_s", {}).get("gdn.core")
    if sz is None or not steps or not secs or not hasattr(
            counts, "gdn_core_fwd"):
        return None
    st = ctx["stats"]
    args = (st["batch"], sz.Hk, sz.Hv, st["seq"], sz.ghd, sz.ghd, sz.chunk)
    least, bounds = 0.0, []
    for cost in (counts.gdn_core_fwd(*args), counts.gdn_core_bwd(*args)):
        s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
        least, bounds = least + s, bounds + [bound]
    layers = sum(m == "gdn" for m, _ in sz.kinds)
    ctx.setdefault("notes", {})["gdn_core"] = {
        "bound": bounds, "layers": layers, "steps_traced": steps,
        "ms_a_step": 1e3 * secs / steps, "least_ms_a_layer": 1e3 * least}
    return 100.0 * least * layers * steps / secs


def flash_roofline_pct(ctx, kernels, cost_name):
    """The calls of `kernels` under `gattn` against the counts module's
    `cost_name` at the stack's sizes, a call: least seconds x calls over the
    device seconds they took."""
    sz, counts = sizes_and_counts(ctx)
    found = picture(ctx).get("kernels", {})
    rows = [found[k] for k in kernels if k in found]
    if sz is None or len(rows) != len(kernels) or not hasattr(
            counts, cost_name):
        return None
    st = ctx["stats"]
    cost = getattr(counts, cost_name)(st["batch"], sz.H, sz.KVH, st["seq"],
                                      sz.hd)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls, secs = rows[0][0], sum(r[1] for r in rows)
    ctx.setdefault("notes", {})["gattn_" + cost_name] = {
        "bound": bound, "calls": calls, "ms_a_call": 1e3 * secs / calls}
    return 100.0 * least_s * calls / secs
