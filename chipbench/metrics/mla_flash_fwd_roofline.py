"""The flash forward kernel's share of its roofline where it serves latent
attention (keys 192 wide, values 128): as flash_fwd_roofline, with the
counts of reduce/mla_counts.py. layer: kernels; moves train_tok_s_chip."""
from chipbench.metrics import _hybrid
from chipbench.reduce import mla_counts


def read(ctx):
    return _hybrid.flash_roofline_pct(ctx, r"flash_fwd", mla_counts.flash_fwd)
