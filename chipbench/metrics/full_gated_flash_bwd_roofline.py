"""The flash backward kernels' (dQ and dK/dV together) share of their
roofline on the full (gated, YaRN) attention layers of a stack whose sliding
layers have another head count, in a traced training run:
reduce/laguna_counts.py `full_flash_bwd` at the FULL layers' heads over the
mean device time of a `flash_dq` and a `flash_dkv` event traced under `gattn`
and not under `swa` (metrics/_mixed_heads.py). layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _mixed_heads


def read(ctx):
    return _mixed_heads.roofline_pct(ctx, "attn", ["flash_dq", "flash_dkv"],
                                     "full_flash_bwd")
