"""The flash backward kernels' (dQ and dK/dV together) share of their
roofline on the sliding layers (window 512) of a traced training run: the
band's operations (10 B H D a pair at the sliding layers' own head count)
and bytes of one layer's backward (reduce/laguna_counts.py `band_flash_bwd`,
peaks.json) over the mean device time of a `flash_dq` and a `flash_dkv`
event traced under the `swa` scope (metrics/_mixed_heads.py). layer:
kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _mixed_heads


def read(ctx):
    return _mixed_heads.roofline_pct(ctx, "swa", ["flash_dq", "flash_dkv"],
                                     "band_flash_bwd")
