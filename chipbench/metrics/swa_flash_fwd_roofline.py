"""The flash forward kernel's share of its roofline on the windowed layers
of a traced training run: the least seconds the chip could take for the
band's operations (4 B H D a pair, S W - W (W - 1) / 2 pairs) and bytes of
one call (reduce/mellum2_counts.py `swa_flash_fwd`, peaks.json) over the
mean device time of a `flash_fwd` event traced under the `swa` scope
(metrics/_routed.py). A kernel that works whole grid blocks where the band
covers half of one reads half of what one that honours the band inside a
block does. layer: kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _routed


def read(ctx):
    return _routed.swa_roofline_pct(ctx, ["flash_fwd"], "swa_flash_fwd")
