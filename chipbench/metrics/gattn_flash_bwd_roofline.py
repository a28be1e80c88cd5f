"""The two flash backward kernels' share of their roofline on the gated
attention layer of a traced training run: the least seconds for one layer's
backward (reduce/qwen3_next_counts.py `flash_bwd`) over the device time of
one `flash_dq` and one `flash_dkv` event traced under the `gattn` scope
(metrics/_gdn.py). layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _gdn


def read(ctx):
    return _gdn.flash_roofline_pct(ctx, ["flash_dq", "flash_dkv"],
                                   "flash_bwd")
