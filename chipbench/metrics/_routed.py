"""Shared by the per-layer metrics of a `train_stack_routed` cell (a `stack`
configuration with windowed attention layers and a held range of experts):
the flash kernels' device time by whether a call ran under the windowed
mixer's `swa` scope, device time by scope over this file's own list, and
what `_stack` already gives (sizes and counts module, steps in the traced
window).

The profiler's op events carry a kernel's HLO text (from which
reduce/xplane.py names it by its operand and result counts) and, in their
metadata, the name stack it was traced under (reduce/scopes.py); neither
reader has both. An op's HLO name is unique in its program, so the two are
joined on it. The grouped products of the held experts (`lax.ragged_dot`)
reach the trace as Mosaic calls the compiler names `ragged-dot-*` and gives
no name stack, so they lie outside the `moe.experts` scope they were traced
under: `ragged_dot_seconds` finds them by that name. Every reader returns None where its scope or kernels are not
in the trace (an older program, another cell, an untraced run)."""
import glob
import os
from collections import defaultdict

from chipbench.metrics._stack import sizes_and_counts, steps_traced  # noqa: F401
from chipbench.reduce import scopes, xplane

SCOPES = ("swa", "moe.route", "moe.experts")
KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def kernel_seconds(events, labels, scope="swa"):
    """{kernel: [calls, seconds]} a device, over scope events [plane, op,
    start_ns, dur_ns, tf_op] whose op `labels` (op -> xplane shape label)
    names as one of KERNELS; {"in": ..., "out": ...} by whether the name
    stack has `scope`."""
    planes = sorted({e[0] for e in events})
    out = {"in": defaultdict(lambda: [0.0, 0.0]),
           "out": defaultdict(lambda: [0.0, 0.0])}
    for _, op, _, dur, tf_op in events:
        kernel = labels.get(op, "").rsplit("__", 1)[-1]
        if kernel not in KERNELS:
            continue
        side = "in" if scopes.scope_of(tf_op, (scope,)) == scope else "out"
        out[side][kernel][0] += 1.0 / len(planes)
        out[side][kernel][1] += dur / 1e9 / len(planes)
    return {k: dict(v) for k, v in out.items()}


def ragged_dot_seconds(events):
    """Device seconds, a device, of the `ragged-dot-*` ops (the grouped
    products and their metadata calls)."""
    planes = {e[0] for e in events}
    ns = sum(e[3] for e in events if e[1].lstrip("%").startswith("ragged-dot"))
    return ns / 1e9 / max(len(planes), 1)


def _trace_file(ctx):
    from chipbench import inworker

    files = glob.glob(os.path.join(inworker.TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    return files[0] if files and ctx.get("trace") else None


def picture(ctx):
    """{"busy_s", "scope_s", "kernels", "ragged_dot_s"} of the run's trace
    file, once a run
    (ctx["routed"]); {} when the run was not traced or no op carries a
    scope of SCOPES."""
    if "routed" not in ctx:
        path, pic = _trace_file(ctx), {}
        if path:
            events = scopes.load(path)
            pic = scopes.by_scope(events, SCOPES)
            if any(s in pic.get("scope_s", {}) for s in SCOPES):
                labels = {e[2]: e[5] for e in xplane.load(path)
                          if e[1] == "XLA Ops"}
                pic["kernels"] = kernel_seconds(events, labels)
                pic["ragged_dot_s"] = ragged_dot_seconds(events)
                ctx.setdefault("notes", {})["routed"] = {
                    k: pic[k] for k in ("scope_s", "kernels", "ragged_dot_s")}
            else:
                pic = {}
        ctx["routed"] = pic
    return ctx["routed"]


def scope_share_pct(ctx, name):
    pic = picture(ctx)
    if not pic.get("busy_s") or name not in pic["scope_s"]:
        return None
    return 100.0 * pic["scope_s"][name] / pic["busy_s"]


def swa_roofline_pct(ctx, kernels, cost_name):
    """The windowed calls of `kernels` against the band's own operations and
    bytes (the counts module's `cost_name`), a call: least seconds x calls
    over the device seconds they took."""
    from chipbench.reduce import flash_counts

    sz, counts = sizes_and_counts(ctx)
    found = picture(ctx).get("kernels", {}).get("in", {})
    rows = [found[k] for k in kernels if k in found]
    if sz is None or len(rows) != len(kernels) or not hasattr(
            counts, cost_name):
        return None
    st = ctx["stats"]
    cost = getattr(counts, cost_name)(st["batch"], sz.H, sz.KVH, st["seq"],
                                      sz.hd, sz.window)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls, secs = rows[0][0], sum(r[1] for r in rows)
    ctx.setdefault("notes", {})[cost_name] = {
        "bound": bound, "calls": calls, "ms_a_call": 1e3 * secs / calls}
    return 100.0 * least_s * calls / secs
