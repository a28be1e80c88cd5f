"""Worker-level TPU chip assignment (own module: needs a fresh
cluster with RTPU_NUM_TPUS set before init, which the module-scoped
ray_start_regular fixture would prevent)."""
def test_worker_chip_isolation(monkeypatch):
    """Unit-instance accounting end-to-end: concurrently-alive TPU actors
    get disjoint TPU_VISIBLE_CHIPS slices of the node's pool, and chips
    return to the pool when workers die (reference: per-instance GPU
    accounting + tpu.py TPU_VISIBLE_CHIPS isolation)."""
    import os

    import ray_tpu

    monkeypatch.setenv("RTPU_NUM_TPUS", "4")
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(num_tpus=2)
        class Holder:
            def chips(self):
                ids = ray_tpu.get_runtime_context() \
                    .get_accelerator_ids()["TPU"]
                return os.getpid(), ids

        a, b = Holder.remote(), Holder.remote()
        (pid_a, chips_a), (pid_b, chips_b) = ray_tpu.get(
            [a.chips.remote(), b.chips.remote()], timeout=60)
        assert pid_a != pid_b
        assert len(chips_a) == 2 and len(chips_b) == 2
        assert not (set(chips_a) & set(chips_b)), (chips_a, chips_b)
        assert set(chips_a) | set(chips_b) == {"0", "1", "2", "3"}
    finally:
        ray_tpu.shutdown()


def test_chip_count_aware_worker_reuse(monkeypatch):
    """A num_tpus=4 task must not reuse an idle worker that sees one chip
    (review scenario: spawn-time visibility vs per-task reservation)."""
    import os

    import ray_tpu

    monkeypatch.setenv("RTPU_NUM_TPUS", "4")
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(num_tpus=1)
        def one_chip():
            return (os.getpid(),
                    ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"])

        @ray_tpu.remote(num_tpus=4)
        def four_chip():
            return (os.getpid(),
                    ray_tpu.get_runtime_context().get_accelerator_ids()["TPU"])

        pid1, chips1 = ray_tpu.get(one_chip.remote(), timeout=60)
        assert len(chips1) == 1
        # The 1-chip worker is now idle; the 4-chip task needs a different
        # worker. With 3 chips left free the spawner can't grant 4, so the
        # new worker runs unrestricted — never a partial slice.
        pid4, chips4 = ray_tpu.get(four_chip.remote(), timeout=60)
        assert pid4 != pid1
        assert chips4 == [] or len(chips4) == 4, chips4
    finally:
        ray_tpu.shutdown()


def test_chips_return_only_when_their_process_is_gone(monkeypatch):
    """libtpu holds a chip until its process has exited: a killed worker's
    chips rejoin the pool then, not when the worker is declared dead, so a
    successor is never spawned onto a chip that is still open."""
    import os

    import ray_tpu

    monkeypatch.setenv("RTPU_NUM_TPUS", "2")
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def __init__(self, predecessor=None):
                self.predecessor_alive = False
                if predecessor is not None:
                    try:
                        os.kill(predecessor, 0)
                        self.predecessor_alive = True
                    except ProcessLookupError:
                        pass

            def linger_on_exit(self):
                """Make this process slow to die, like a TPU runtime
                tearing down its DMA mappings."""
                import ctypes
                import signal
                import time

                # Ignore SIGTERM (libc: this is not the main thread) and
                # dawdle in the hard exit the shutdown message ends in.
                ctypes.CDLL(None).signal(signal.SIGTERM, 1)  # SIG_IGN
                hard_exit = os._exit
                os._exit = lambda code: (time.sleep(1.5), hard_exit(code))
                return os.getpid()

            def facts(self):
                ids = ray_tpu.get_runtime_context() \
                    .get_accelerator_ids()["TPU"]
                return ids, self.predecessor_alive

        a, b = Holder.remote(), Holder.remote()
        pid_a = ray_tpu.get(a.linger_on_exit.remote(), timeout=60)
        chips_a, _ = ray_tpu.get(a.facts.remote(), timeout=60)
        chips_b, _ = ray_tpu.get(b.facts.remote(), timeout=60)
        ray_tpu.kill(a)
        c = Holder.remote(pid_a)
        chips_c, predecessor_alive = ray_tpu.get(c.facts.remote(), timeout=60)
        assert chips_c == chips_a and chips_c != chips_b
        assert not predecessor_alive, \
            "successor started on a chip its predecessor still held"
    finally:
        ray_tpu.shutdown()
