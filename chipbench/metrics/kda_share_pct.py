"""Share of the device's busy time in the traced window spent in the KDA
core (the `kda.core` named scope of models/transformer.py: ops/kda.py's
chunked gated delta rule, forward, backward and remat re-runs; the layer's
projections, convolutions and gates are outside it). From the ops' name
stacks in the trace (reduce/scopes.py). None when no op carries the scope.
layer: kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _hybrid


def read(ctx):
    return _hybrid.scope_share_pct(ctx, ["kda.core"])
