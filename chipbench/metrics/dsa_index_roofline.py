"""The lightning indexer as a share of its roofline in a traced training run:
the least seconds the chip could take for the scores of the triangle (once
for the selection, again with three transposed products for the loss and its
gradient) and the attention's probabilities of the kept pairs the loss's
target needs, every layer of one step (reduce/keye_vl2_counts.py `dsa_index`;
peaks.json), over the device seconds a step spends in the `dsa.index` scope
(metrics/_sparse.py; the exact top-k's counting passes and the indexer's
projections are in the measured time and not in the count). layer: kernels;
moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _sparse


def read(ctx):
    def costs(counts, sz, st):
        return [counts.dsa_index(st["batch"], sz.H, sz.KVH, st["seq"], sz.hd,
                                 sz.HI, sz.dI, sz.topk)]

    return _sparse.roofline_pct(ctx, "dsa.index", costs)
