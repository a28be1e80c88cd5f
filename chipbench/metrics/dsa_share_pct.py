"""Share of the device's busy time in the traced window spent under the
learned-sparse-attention mixer's `dsa` scope (the q / k / v projections, the
q / k norms and M-RoPE, the output projection) with `dsa.index` (the indexer's
projections, scores, exact selection, loss and gradient) and `dsa.core`
(attention over the selection) inside it, forward and backward.
metrics/_sparse.py. layer: kernels; moves train_tok_s_chip; source
device_trace."""
from chipbench.metrics import _sparse


def read(ctx):
    return _sparse.share_pct(ctx, _sparse.SCOPES)
