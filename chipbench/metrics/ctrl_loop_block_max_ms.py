"""Longest single stretch for which a controller handler (`ctrl.rpc.<kind>`)
or periodic body (`ctrl.periodic.<loop>`) held the controller's event loop
in this run, set-up included, from the runner's own phase table (the runner
hosts the controller; every RPC of the cluster and every relayed token
waits behind that loop). Which one it was is in ctx["notes"]. None when the
program keeps no such table. layer: driver API / cluster control; moves
setup_s; source program_span."""
from chipbench.reduce import host_spans


def read(ctx):
    return host_spans.picture(ctx).get("ctrl_loop_block_max_ms")
