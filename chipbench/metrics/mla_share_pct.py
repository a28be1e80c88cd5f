"""Share of the device's busy time in the traced window spent under the
latent mixer's `mla` scope where it rotates (projections, latent norm, the
`mla.rope` rotation, broadcast and concatenation, the three flash kernels
and the transposes XLA leaves beside them), forward and backward.
metrics/_latent.py. layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _latent


def read(ctx):
    return _latent.share_pct(ctx, kernels=True)
