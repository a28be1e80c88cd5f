"""The gated short convolution core's share of its roofline in a traced
training run: the least seconds the chip could take for the bytes of
Cg * conv(Bg * x) of every convolution layer, forward and backward of one
step (reduce/lfm2_moe_counts.py: 22 d bytes a token and layer in bfloat16
over HBM's rate, peaks.json), over the device seconds a step spends in the
`shortconv.core` scope, whichever body implements it.
metrics/_shortconv.py. layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _shortconv


def read(ctx):
    return _shortconv.core_roofline_pct(ctx)
