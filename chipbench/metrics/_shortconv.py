"""Shared by the per-layer metrics of a `stack` configuration with gated
short convolution layers (configs/lfm2_8b_a1b.json): device time under the
scope models/transformer.py opens round that mixer, `shortconv`, with
`shortconv.core` inside it (Cg * conv(Bg * x); ops/shortconv.py opens it
round its backward kernel too, because a backward rule is traced outside
the mixer), from the ops' name stacks in the trace (reduce/scopes.py).
Every reader returns None where neither scope is in the trace (an older
program, another cell, an untraced run)."""
from chipbench.metrics import _routed
from chipbench.metrics._stack import sizes_and_counts, steps_traced
from chipbench.reduce import scopes

SCOPES = ("shortconv.core", "shortconv")  # the inner scope first


def picture(ctx):
    """scopes.by_scope of the run's trace file over SCOPES, once a run
    (ctx["shortconv"]); {} when the run was not traced or no op carries
    either scope."""
    if "shortconv" not in ctx:
        path, pic = _routed._trace_file(ctx), {}
        if path:
            pic = scopes.by_scope(scopes.load(path), SCOPES)
            if not any(s in pic.get("scope_s", {}) for s in SCOPES):
                pic = {}
        if pic:
            ctx.setdefault("notes", {})["shortconv_scope_s"] = pic["scope_s"]
        ctx["shortconv"] = pic
    return ctx["shortconv"]


def share_pct(ctx):
    """Both scopes' share of the busy time, together; None where the mixer's
    scope is not in the trace."""
    pic = picture(ctx)
    if not pic.get("busy_s") or "shortconv" not in pic["scope_s"]:
        return None
    return 100.0 * sum(pic["scope_s"].get(n, 0.0) for n in SCOPES) / pic[
        "busy_s"]


def core_roofline_pct(ctx):
    """The `shortconv.core` scope's device seconds a step against the least
    the chip could take for the core's bytes of every convolution layer,
    forward and backward (reduce/lfm2_moe_counts.py; whichever body runs it,
    its recomputation under remat in the time and not in the count)."""
    (sz, counts), steps = sizes_and_counts(ctx), steps_traced(ctx)
    secs = picture(ctx).get("scope_s", {}).get("shortconv.core")
    if sz is None or not steps or not secs or not hasattr(
            counts, "shortconv_core"):
        return None
    st = ctx["stats"]
    cost = counts.shortconv_core(st["batch"], st["seq"], sz.d, sz.K)
    least_s = counts.roofline_s(cost, ctx["peaks"])
    layers = sum(m == "shortconv" for m, _ in sz.kinds)
    ctx.setdefault("notes", {})["shortconv_core"] = {
        "bound": "memory", "layers": layers, "steps_traced": steps,
        "ms_a_step": 1e3 * secs / steps, "least_ms_a_layer": 1e3 * least_s}
    return 100.0 * least_s * layers * steps / secs
