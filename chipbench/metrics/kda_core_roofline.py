"""The KDA core's share of its roofline in a traced training run: the least
seconds the chip could take for the chunked algorithm's operations and bytes
of every KDA layer, forward and backward of one step
(reduce/kda_counts.py, peaks.json), over the device seconds a step spends in
the `kda.core` scope (reduce/scopes.py; the remat re-run of the forward is
in the measured time and not in the count). layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _hybrid
from chipbench.reduce import flash_counts, kda_counts, scopes


def read(ctx):
    sz, steps = _hybrid.sizes(ctx), _hybrid.steps_traced(ctx)
    secs = scopes.picture(ctx).get("scope_s", {}).get("kda.core")
    if sz is None or not steps or not secs:
        return None
    st = ctx["stats"]
    layers = sum(m == "kda" for m, _ in sz.kinds)
    chunk = ctx["cell"]["config"]["transformer_config"]["kda_chunk"]
    cost = kda_counts.kda_core(st["batch"], sz.kda_H, st["seq"], sz.kda_hd,
                               sz.kda_hd, chunk)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    ctx.setdefault("notes", {})["kda_core"] = {
        "bound": bound, "layers": layers, "steps_traced": steps,
        "ms_a_step": 1e3 * secs / steps}
    return 100.0 * least_s * layers * steps / secs
