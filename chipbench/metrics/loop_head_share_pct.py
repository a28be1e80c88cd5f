"""Share of the device's busy time in the traced window spent under the
`loop.head` scope of a looped stack: every pass's final norm, head product,
cross-entropy and exit gate, forward, recomputed and backward (four heads a
step over one `lm_head`). metrics/_loop.py. layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _loop


def read(ctx):
    return _loop.head_share_pct(ctx)
