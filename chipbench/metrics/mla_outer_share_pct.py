"""Share of the device's busy time in the traced window spent under the
latent mixer's `mla` scope and outside its three flash kernels: the four
projections, the latent norm, the rotation (`mla.rope`), the shared key
part's broadcast over the heads, the concatenations and transposes: what a
change to how the key part reaches the kernel moves. `mla_share_pct` less
this is the kernels'. metrics/_latent.py. layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _latent


def read(ctx):
    return _latent.share_pct(ctx, kernels=False)
