"""Share of the device's busy time in the traced window spent routing
(`moe.route`: sigmoid scores, top-8, the sort into the buffer) and in the
held experts (`moe.experts`: gather, two grouped matrix products, scatter),
forward and backward; the shared expert is outside both. reduce/scopes.py.
layer: kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _hybrid


def read(ctx):
    return _hybrid.scope_share_pct(ctx, ["moe.route", "moe.experts"])
