"""Share of the device's busy time in the traced window spent in the Mamba-2
layer OUTSIDE its core: the `mamba` named scope of models/transformer.py less
`ssd.core` (in_proj's three matmuls, the convolution, SiLU, softplus, the
gated norm, out_proj; forward, backward and remat re-runs). From the ops'
name stacks in the trace (reduce/scopes.py lists the inner scope first, so
`mamba` holds what is under it and not under `ssd.core`). None when no op
carries the scope. layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _stack


def read(ctx):
    return _stack.scope_share_pct(ctx, "mamba")
