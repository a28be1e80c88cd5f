"""Attention over the selected sets as a share of its roofline in a traced
training run: the least seconds the chip could take for the KEPT pairs'
operations and bytes of every layer, forward and backward of one step
(reduce/keye_vl2_counts.py `dsa_core_fwd` / `dsa_core_bwd`: 4 and 10 B H D a
kept pair, whatever implements them; peaks.json), over the device seconds a
step spends in the `dsa.core` scope (metrics/_sparse.py). The thresholded
kernels visit every earlier key, 8.3 times the kept pairs at 32,768: the
share says what the selection leaves on the table. layer: kernels; moves
train_tok_s_chip; source device_trace."""
from chipbench.metrics import _sparse


def read(ctx):
    def costs(counts, sz, st):
        args = (st["batch"], sz.H, sz.KVH, st["seq"], sz.hd, sz.topk)
        return [counts.dsa_core_fwd(*args), counts.dsa_core_bwd(*args)]

    return _sparse.roofline_pct(ctx, "dsa.core", costs)
