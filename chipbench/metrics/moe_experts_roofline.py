"""The held experts' share of their roofline in a traced training run: the
least seconds the chip could take for the grouped products' operations
(6 x 3 x d x F a worked row, forward and backward) and bytes of one step
(reduce/mellum2_counts.py `experts`, peaks.json), for the rows the step's
own counters say were assigned to held experts (`stats.moe_assigned_a_step`,
every expert layer), over the device seconds a step spends on the held
experts: the `moe.experts` scope (gather, masks, SiLU, scatter and their
transposes) plus the grouped products' own Mosaic calls, which the compiler
emits with no name stack and so outside every scope (`ragged-dot-*` ops;
metrics/_routed.py). `moe_share_pct` reads the scopes alone and leaves those
calls out. layer: kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _routed
from chipbench.reduce import flash_counts


def read(ctx):
    (sz, counts), steps = _routed.sizes_and_counts(ctx), _routed.steps_traced(ctx)
    pic = _routed.picture(ctx)
    secs = pic.get("scope_s", {}).get("moe.experts")
    rows = ctx["stats"].get("moe_assigned_a_step")
    if sz is None or not steps or not secs or not rows or not hasattr(
            counts, "experts"):
        return None
    layers = sum(f == "moe" for _, f in sz.kinds)
    cost = counts.experts(rows, sz.held * layers, sz.d, sz.Fe)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    products = pic.get("ragged_dot_s", 0.0)
    ctx.setdefault("notes", {})["moe_experts"] = {
        "bound": bound, "rows_a_step": rows, "steps_traced": steps,
        "scope_ms_a_step": 1e3 * secs / steps,
        "ragged_dot_ms_a_step": 1e3 * products / steps}
    return 100.0 * least_s * steps / (secs + products)
