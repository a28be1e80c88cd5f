"""Shared by the per-layer metrics of a `stack` configuration whose every
mixer is latent attention (configs/kanana_2_30b_a3b.json): device time under
the `mla` scope and under `mla.rope` inside it (models/transformer.py
`_mla_mixer`), and the three flash kernels' calls and seconds under `mla`
(`_routed.kernel_seconds`: a kernel named from its HLO text by
reduce/xplane.py, its mixer from its op's name stack by reduce/scopes.py),
held to reduce/mla_counts.py at the sizes of the `stack` section. The
hybrid cell's `mla_flash_*_roofline` read the same kernels by the same
counts with `_hybrid.sizes`, which sizes Kimi-Linear only. Every reader
returns None where the `mla.rope` scope or the kernels are not in the trace
(an older program, another cell, an untraced run)."""
from chipbench.metrics import _routed
from chipbench.metrics._stack import sizes_and_counts
from chipbench.reduce import flash_counts, scopes, xplane

SCOPES = ("mla.rope", "mla")  # the inner scope first


def picture(ctx):
    """{"busy_s", "scope_s", "kernels"} of the run's trace file, once a run
    (ctx["latent"]); {} when the run was not traced or no op carries the
    `mla.rope` scope (a latent layer that does not rotate is the hybrid
    cell's, with readers of its own)."""
    if "latent" not in ctx:
        path, pic = _routed._trace_file(ctx), {}
        if path:
            events = scopes.load(path)
            pic = scopes.by_scope(events, SCOPES)
            if "mla.rope" in pic.get("scope_s", {}):
                labels = {e[2]: e[5] for e in xplane.load(path)
                          if e[1] == "XLA Ops"}
                pic["kernels"] = _routed.kernel_seconds(
                    events, labels, scope="mla")["in"]
                ctx.setdefault("notes", {})["latent"] = {
                    k: pic[k] for k in ("scope_s", "kernels")}
            else:
                pic = {}
        ctx["latent"] = pic
    return ctx["latent"]


def share_pct(ctx, kernels: bool):
    """The `mla` scope's share of the busy time, `mla.rope` and the flash
    kernels inside it; without the kernels where `kernels` is false."""
    pic = picture(ctx)
    if not pic.get("busy_s"):
        return None
    secs = sum(pic["scope_s"].get(s, 0.0) for s in SCOPES)
    if not kernels:
        secs -= sum(v[1] for v in pic["kernels"].values())
    return 100.0 * secs / pic["busy_s"]


def roofline_pct(ctx, kernels, cost_name):
    """The calls of `kernels` under `mla` against the counts module's
    `cost_name` at the stack's sizes, a call: least seconds x calls over the
    device seconds they took."""
    sz, counts = sizes_and_counts(ctx)
    found = picture(ctx).get("kernels", {})
    rows = [found[k] for k in kernels if k in found]
    if sz is None or len(rows) != len(kernels) or not hasattr(
            counts, cost_name):
        return None
    st = ctx["stats"]
    cost = getattr(counts, cost_name)(st["batch"], sz.H, st["seq"],
                                      sz.nope + sz.rope, sz.dv)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls, secs = rows[0][0], sum(r[1] for r in rows)
    ctx.setdefault("notes", {})["mla_stack_" + cost_name] = {
        "bound": bound, "calls": calls, "ms_a_call": 1e3 * secs / calls}
    return 100.0 * least_s * calls / secs
