"""Host-level collectives over the actor rendezvous (ray.util.collective parity)."""
import numpy as np

import ray_tpu


def _worker(world_size, rank, group_name):
    from ray_tpu.parallel import collectives as col

    g = col.init_collective_group(world_size, rank, group_name)
    out = {}
    out["allreduce"] = g.allreduce(np.full((4,), float(rank + 1), np.float32))
    out["mean"] = g.allreduce(np.full((2,), float(rank), np.float32), op="mean")
    out["gathered"] = g.allgather(rank * 10)
    out["bcast"] = g.broadcast("hello" if rank == 0 else None, src_rank=0)
    g.barrier()
    out["rs"] = g.reducescatter(np.arange(4, dtype=np.float32))
    return out


def test_collective_group_two_ranks(ray_start_regular):
    worker = ray_tpu.remote(_worker)
    refs = [worker.remote(2, r, "testgrp") for r in range(2)]
    res = ray_tpu.get(refs, timeout=120)
    for r in (0, 1):
        np.testing.assert_array_equal(res[r]["allreduce"], np.full((4,), 3.0))
        np.testing.assert_array_equal(res[r]["mean"], np.full((2,), 0.5))
        assert res[r]["gathered"] == [0, 10]
        assert res[r]["bcast"] == "hello"
    # reducescatter: rank r gets slice r of 2*[0,1,2,3]
    np.testing.assert_array_equal(res[0]["rs"], np.array([0.0, 2.0]))
    np.testing.assert_array_equal(res[1]["rs"], np.array([4.0, 6.0]))


def test_collective_pytree_allreduce(ray_start_regular):
    def tree_worker(ws, rank):
        from ray_tpu.parallel import collectives as col

        g = col.init_collective_group(ws, rank, "treegrp")
        tree = {"a": np.ones(3, np.float32) * (rank + 1), "b": [np.zeros(2) + rank]}
        return g.allreduce(tree)

    worker = ray_tpu.remote(tree_worker)
    res = ray_tpu.get([worker.remote(2, r) for r in range(2)], timeout=120)
    np.testing.assert_array_equal(res[0]["a"], np.full(3, 3.0))
    np.testing.assert_array_equal(res[0]["b"][0], np.full(2, 1.0))


def test_graft_entry_dryrun():
    """The driver-facing multichip dry-run must compile and execute."""
    import subprocess
    import sys
    import os

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    out = subprocess.run(
        [sys.executable, "-c", "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "dryrun_multichip ok" in out.stdout
