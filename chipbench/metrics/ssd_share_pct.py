"""Share of the device's busy time in the traced window spent in the Mamba-2
core (the `ssd.core` named scope of models/transformer.py: ops/ssd.py's
chunked scan, forward, backward and remat re-runs; the layer's projections,
convolution, gate and norm are outside it). From the ops' name stacks in the
trace (reduce/scopes.py). None when no op carries the scope. layer: kernels;
moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _stack


def read(ctx):
    return _stack.scope_share_pct(ctx, "ssd.core")
