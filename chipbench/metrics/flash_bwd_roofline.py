"""The flash backward kernels' share of their roofline: the dq and dkv
kernels of one layer together against the 5 half-matrix matmuls the backward
needs (reduce/flash_counts.py). layer: kernels; moves train_tok_s_chip."""
from chipbench.metrics import _flash
from chipbench.reduce import flash_counts


def read(ctx):
    r = _flash.roofline_pct(ctx, r"flash_d(q|kv)", flash_counts.flash_bwd)
    if r is None:
        return None
    calls, secs, least_s = r
    return 100.0 * least_s * (calls / 2.0) / secs  # a dq + dkv pair a layer
