"""Share of the device's busy time in the traced window spent under
`dsa.index`: the lightning indexer's three projections, its scores of every
earlier key, the exact top-k (the threshold's bisection and the tie rule), the
bits, and the indexer's loss with its gradient. metrics/_sparse.py. layer:
kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _sparse


def read(ctx):
    return _sparse.share_pct(ctx, ("dsa.index",))
