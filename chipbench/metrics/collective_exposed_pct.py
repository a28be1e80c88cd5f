"""Share of the traced window a device spends inside collective ops
(all-reduce, all-gather, reduce-scatter, all-to-all, collective-permute and
their -start/-done halves) on its op line. Ops run one at a time there, so
time inside a collective op is time no compute op runs: the exposed part.
Transfers that overlap compute run on the DMA engines and are not on that
line. layer: train step (mesh); moves train_tok_s_chip."""


def read(ctx):
    tr = ctx.get("trace") or {}
    if not tr.get("window_s") or tr.get("devices", 0) < 2:
        return None
    return 100.0 * tr["collective_s"] / tr["window_s"]
