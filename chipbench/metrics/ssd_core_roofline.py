"""The Mamba-2 core's share of its roofline in a traced training run: the
least seconds the chip could take for the chunked algorithm's operations and
bytes of every Mamba-2 layer, forward and backward of one step
(reduce/ssd_counts.py, peaks.json), over the device seconds a step spends in
the `ssd.core` scope (reduce/scopes.py; the remat re-run of the forward is
in the measured time and not in the count). layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _stack
from chipbench.reduce import flash_counts


def read(ctx):
    (sz, counts), steps = _stack.sizes_and_counts(ctx), _stack.steps_traced(ctx)
    secs = _stack.picture(ctx).get("scope_s", {}).get("ssd.core")
    if sz is None or not steps or not secs:
        return None
    st = ctx["stats"]
    layers = sum(m == "mamba2" for m, _ in sz.kinds)
    cost = counts.ssd_core(st["batch"], st["seq"], sz.Hm, sz.P, sz.N, sz.G,
                           sz.chunk)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    ctx.setdefault("notes", {})["ssd_core"] = {
        "bound": bound, "layers": layers, "steps_traced": steps,
        "ms_a_step": 1e3 * secs / steps}
    return 100.0 * least_s * layers * steps / secs
