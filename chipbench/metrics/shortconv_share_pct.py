"""Share of the device's busy time in the traced window spent in the gated
short convolution mixers: the `shortconv` named scope of
models/transformer.py with `shortconv.core` inside it (the joint projection,
Cg * conv(Bg * x), the output projection; forward, backward and remat
re-runs). metrics/_shortconv.py. layer: kernels; moves train_tok_s_chip;
source device_trace."""
from chipbench.metrics import _shortconv


def read(ctx):
    return _shortconv.share_pct(ctx)
