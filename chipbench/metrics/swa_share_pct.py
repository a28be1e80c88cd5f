"""Share of the device's busy time in the traced window spent under the
windowed mixer's `swa` scope (the banded flash forward, dQ and dK/dV calls
and the transposes XLA leaves beside them), forward and backward;
`flash_share_pct` less the kernels' part of it is the full layer's.
metrics/_routed.py. layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _routed


def read(ctx):
    return _routed.scope_share_pct(ctx, "swa")
