"""The flash forward kernel's share of its roofline in a traced training
run: least seconds the chip could take for one call's operations and bytes
(reduce/flash_counts.py, peaks.json) over the mean device time of a
`flash_fwd` event. Every forward call counts, the remat re-run too (it is a
call of the kernel; what the step pays for recomputation shows in
train_mfu_pct). layer: kernels; moves train_tok_s_chip."""
from chipbench.metrics import _flash
from chipbench.reduce import flash_counts


def read(ctx):
    r = _flash.roofline_pct(ctx, r"flash_fwd", flash_counts.flash_fwd)
    if r is None:
        return None
    calls, secs, least_s = r
    return 100.0 * least_s * calls / secs
