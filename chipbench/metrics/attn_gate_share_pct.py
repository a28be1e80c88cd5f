"""Share of the device's busy time in the traced window spent under the
`gattn.gate` scope of every attention layer, sliding and full (the q / k / v
products, the rotation of the kind's columns at the kind's theta, the
per-head sigmoid gate and its product with the attention's output), forward
and backward. metrics/_mixed_heads.py. layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _mixed_heads


def read(ctx):
    return _mixed_heads.gate_share_pct(ctx)
