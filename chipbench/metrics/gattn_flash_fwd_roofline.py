"""The flash forward kernel's share of its roofline on the gated attention
layer (16 query heads over 2 key heads of 256) of a traced training run: the
least seconds the chip could take for one call's operations and bytes
(reduce/qwen3_next_counts.py `flash_fwd`: the triangle's pairs, K and V
once a key head; peaks.json) over the mean device time of a `flash_fwd`
event traced under the `gattn` scope (metrics/_gdn.py). layer: kernels;
moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _gdn


def read(ctx):
    return _gdn.flash_roofline_pct(ctx, ["flash_fwd"], "flash_fwd")
