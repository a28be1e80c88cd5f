"""The flash forward kernel's share of its roofline on the sliding layers
(window 512, MORE query heads than the full layers have) of a traced
training run: the least seconds the chip could take for the band's
operations (4 B H D a pair, S W - W (W - 1) / 2 pairs, at the sliding
layers' own head count) and bytes of one call (reduce/laguna_counts.py
`band_flash_fwd`, peaks.json) over the mean device time of a `flash_fwd`
event traced under the `swa` scope (metrics/_mixed_heads.py). layer:
kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _mixed_heads


def read(ctx):
    return _mixed_heads.roofline_pct(ctx, "swa", ["flash_fwd"],
                                     "band_flash_fwd")
