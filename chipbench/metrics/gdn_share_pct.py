"""Share of the device's busy time in the traced window spent under the
Gated DeltaNet mixer's `gdn` scope (the joint projections, the convolution,
the L2 norms, the decay and beta, the `gdn.core` delta rule, the gated norm
and the output projection), forward and backward. metrics/_gdn.py. layer:
kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _gdn


def read(ctx):
    return _gdn.share_pct(ctx, core=True)
