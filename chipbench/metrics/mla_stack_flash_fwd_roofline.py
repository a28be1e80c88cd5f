"""The flash forward kernel's share of its roofline on the latent layers
(keys 192 wide, values 128) of a traced training run of a `stack`
configuration: the least seconds the chip could take for one call's
operations and bytes (reduce/mla_counts.py `flash_fwd` through the
configuration's counts module, peaks.json) over the mean device time of a
`flash_fwd` event traced under the `mla` scope (metrics/_latent.py).
`mla_flash_fwd_roofline` is the same for the hybrid cell. layer: kernels;
moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _latent


def read(ctx):
    return _latent.roofline_pct(ctx, ["flash_fwd"], "flash_fwd")
