"""The delta rule's core at a scalar decay, as a share of its roofline in a
traced training run: the least seconds the chip could take for the chunked
rule's operations and bytes of every DeltaNet layer, forward and backward of
one step (reduce/qwen3_next_counts.py `gdn_core_fwd` / `gdn_core_bwd`:
q and k once a key head, g [B,S,H]; peaks.json), over the device seconds a
step spends in the `gdn.core` scope (metrics/_gdn.py; the remat re-run of
the forward is in the measured time and not in the count). layer: kernels;
moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _gdn


def read(ctx):
    return _gdn.core_roofline_pct(ctx)
