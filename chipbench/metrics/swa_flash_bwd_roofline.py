"""The flash backward kernels' share of their roofline on the windowed
layers of a traced training run: the least seconds the chip could take for
the band's backward operations (10 B H D a pair: five products, S recomputed
among them) and bytes (reduce/mellum2_counts.py `swa_flash_bwd`) over the
device time of a `flash_dq` plus a `flash_dkv` event traced under the `swa`
scope (metrics/_routed.py); each kernel recomputes S and dP, so the two
together are measured against the five products the algorithm needs. layer:
kernels; moves train_tok_s_chip; source device_trace."""
from chipbench.metrics import _routed


def read(ctx):
    return _routed.swa_roofline_pct(ctx, ["flash_dq", "flash_dkv"],
                                    "swa_flash_bwd")
