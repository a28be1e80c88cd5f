"""The two flash backward kernels' share of their roofline on the latent
layers of a traced training run of a `stack` configuration: the dQ and
dK/dV calls traced under the `mla` scope together against the five products
the algorithm needs (reduce/mla_counts.py `flash_bwd` through the
configuration's counts module; metrics/_latent.py).
`mla_flash_bwd_roofline` is the same for the hybrid cell. layer: kernels;
moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _latent


def read(ctx):
    return _latent.roofline_pct(ctx, ["flash_dq", "flash_dkv"], "flash_bwd")
