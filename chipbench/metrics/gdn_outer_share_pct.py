"""Share of the device's busy time in the traced window spent in the Gated
DeltaNet layers OUTSIDE their core: the `gdn` scope less `gdn.core`
(projections, convolution, norms, gates and what XLA leaves beside them).
metrics/_gdn.py. layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _gdn


def read(ctx):
    return _gdn.share_pct(ctx, core=False)
