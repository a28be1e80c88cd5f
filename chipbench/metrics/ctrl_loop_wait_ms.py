"""Of the stretches of 50 ms or more for which a controller handler
(`ctrl.rpc.<kind>`) or periodic body (`ctrl.periodic.<loop>`) held the
controller's event loop in this run, the part in which the loop's thread was
off the CPU: each entry's `dur_ns - cpu_ns`, summed, ms
(reduce/slow_causes.py, from the runner's slow ring). 0.0 when there is no
such stretch. Beside `ctrl_loop_block_max_ms` it says whether seconds under
one handler were computed (move it off the loop) or not. Off the CPU holds
the handler's own blocking calls (a synchronous RPC, a child it waits for,
a read) as well as a thread that was descheduled or waited for the GIL or a
page, and the chip machines' kernel reads the switch counts that would tell
them apart as 0: the evidence for "starved" is in
ctx["notes"]["slow_causes"], where `longest_ctrl` has the stretch's deltas,
the loop's lag over the same seconds and whether the owner's
`runtime.backend_init` ran meanwhile, and `longest_loop_lag` says whether
the loop froze as long with no body on it. None when no entry of the ring carries `cpu_ns`
(an older commit). layer: driver API / cluster control; moves setup_s;
source program_span."""
from chipbench.reduce import slow_causes


def read(ctx):
    return slow_causes.picture(ctx).get("ctrl_loop_wait_ms")
