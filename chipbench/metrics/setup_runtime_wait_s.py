"""Of the runtime's start in the chip-owning worker (`runtime.import_jax`,
`runtime.backend_init`, `runtime.mesh`: what `setup_runtime_init_s` sums),
the seconds its thread was off the CPU: each phase's `dur_ns - cpu_ns`, from
the thread's rusage deltas on the slow-ring entry (reduce/slow_causes.py).
Beside `setup_runtime_init_s` it says whether the seconds that come and go
between runs of one tree were computed or not. Off the CPU holds the
phase's own blocking (libtpu's wait for the chip, a read) as well as a
starved thread; the same phases' `inblock` and `majflt` (was the disk read;
0 on the chip machines' kernel, which does not count them) and whether the
runner's loop froze meanwhile (`longest_loop_lag.in_backend_init`) are in
ctx["notes"]["slow_causes"]. None when the entries carry no `cpu_ns` (an
older commit). layer: runtime; moves setup_s; source program_span."""
from chipbench.reduce import slow_causes


def read(ctx):
    return slow_causes.picture(ctx).get("setup_runtime_wait_s")
