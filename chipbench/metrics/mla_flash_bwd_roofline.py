"""The two flash backward kernels' share of their roofline where they serve
latent attention (keys 192 wide, values 128): as flash_bwd_roofline, the dq
and dkv kernels together against the five products the algorithm needs,
with the counts of reduce/mla_counts.py. layer: kernels; moves
train_tok_s_chip."""
import re

from chipbench.metrics import _hybrid
from chipbench.reduce import flash_counts, mla_counts


def read(ctx):
    tr, sz = ctx.get("trace") or {}, _hybrid.sizes(ctx)
    ops = tr.get("op_self_s", {})
    dq = [k for k in ops if re.search(r"flash_dq", k)]
    dkv = [k for k in ops if re.search(r"flash_dkv", k)]
    if not dq or not dkv or sz is None:
        return None
    st = ctx["stats"]
    cost = mla_counts.flash_bwd(st["batch"], sz.H, st["seq"],
                                sz.nope + sz.rope, sz.dv)
    least_s, bound = flash_counts.roofline_s(cost, ctx["peaks"])
    calls = sum(tr["op_count"][k] for k in dq)
    secs = sum(ops[k] for k in dq + dkv)
    ctx.setdefault("notes", {})["mla_flash_bwd"] = {"bound": bound,
                                                    "calls": calls}
    return 100.0 * least_s * calls / secs
