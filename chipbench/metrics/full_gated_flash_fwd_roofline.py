"""The flash forward kernel's share of its roofline on the full (gated,
YaRN) attention layers of a stack whose sliding layers have another head
count, in a traced training run: the triangle's operations and bytes of one
call at the FULL layers' heads (reduce/laguna_counts.py `full_flash_fwd`,
peaks.json) over the mean device time of a `flash_fwd` event traced under
`gattn` and not under `swa` (metrics/_mixed_heads.py). layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _mixed_heads


def read(ctx):
    return _mixed_heads.roofline_pct(ctx, "attn", ["flash_fwd"],
                                     "full_flash_fwd")
