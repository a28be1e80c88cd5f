"""Shared by the per-layer metrics of a `stack` configuration whose windowed
and full attention layers have different numbers of query heads and a gate
on their output (configs/laguna_s_2_1.json): device time under `gattn.gate`
(models/transformer.py `_attn_mixer`: the q / k / v products, the rotations
and the gates of every attention layer), and the three flash kernels' calls
and seconds by the layer kind they ran for: under `swa` the sliding layers',
outside it (under `gattn` alone) the full layers' (`_routed.kernel_seconds`:
a kernel named from its HLO text by reduce/xplane.py, its mixer from its
op's name stack by reduce/scopes.py), each held to reduce/laguna_counts.py
at the KIND's own head count (`sz.H[kind]`; `_routed.swa_roofline_pct`
sizes a windowed call by one `sz.H`). Every reader returns None where the
`gattn.gate` scope or the kernels are not in the trace (an older program,
another cell, an untraced run) or the sizes carry no head count a kind."""
from chipbench.metrics import _routed
from chipbench.metrics._stack import sizes_and_counts
from chipbench.reduce import flash_counts, scopes, xplane

SCOPES = ("gattn.gate", "swa", "gattn")  # inner scopes first


def kernels_by_kind(events, labels):
    """{"swa" | "attn": {kernel: [calls, seconds]}} a device: the flash
    kernels under `swa`, and the others, which are the full layers' under
    `gattn` alone (a gate is a property of the configuration, so where
    `gattn.gate` is in the trace every attention layer ran under `gattn`)."""
    found = _routed.kernel_seconds(events, labels, scope="swa")
    return {"swa": found["in"], "attn": found["out"]}


def picture(ctx):
    """{"busy_s", "scope_s", "kernels"} of the run's trace file, once a run
    (ctx["mixed_heads"]); {} when the run was not traced or no op carries
    both the `gattn.gate` and the `swa` scope."""
    if "mixed_heads" not in ctx:
        path, pic = _routed._trace_file(ctx), {}
        if path:
            events = scopes.load(path)
            pic = scopes.by_scope(events, SCOPES)
            if all(s in pic.get("scope_s", {}) for s in ("gattn.gate", "swa")):
                labels = {e[2]: e[5] for e in xplane.load(path)
                          if e[1] == "XLA Ops"}
                pic["kernels"] = kernels_by_kind(events, labels)
                ctx.setdefault("notes", {})["mixed_heads"] = {
                    k: pic[k] for k in ("scope_s", "kernels")}
            else:
                pic = {}
        ctx["mixed_heads"] = pic
    return ctx["mixed_heads"]


def gate_share_pct(ctx):
    pic = picture(ctx)
    if not pic.get("busy_s"):
        return None
    return 100.0 * pic["scope_s"]["gattn.gate"] / pic["busy_s"]


def roofline_pct(ctx, kind, kernels, cost_name):
    """The calls of `kernels` a layer of `kind` ("swa" | "attn") made
    against the counts module's `cost_name` at that kind's heads, a call:
    least seconds x calls over the device seconds they took."""
    sz, counts = sizes_and_counts(ctx)
    found = picture(ctx).get("kernels", {}).get(kind, {})
    rows = [found[k] for k in kernels if k in found]
    if sz is None or len(rows) != len(kernels) or not hasattr(
            counts, cost_name) or not isinstance(getattr(sz, "H", None), dict):
        return None
    st = ctx["stats"]
    window = (sz.window,) if kind == "swa" else ()
    cost = getattr(counts, cost_name)(st["batch"], sz.H[kind], sz.KVH,
                                      st["seq"], sz.hd, *window)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls, secs = rows[0][0], sum(r[1] for r in rows)
    ctx.setdefault("notes", {})["mixed_" + cost_name] = {
        "bound": bound, "calls": calls, "ms_a_call": 1e3 * secs / calls}
    return 100.0 * least_s * calls / secs
