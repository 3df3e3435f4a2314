"""End-to-end gang tests: HorovodRunner(np<=-2) spawns a real
multi-process gang on CPU, rendezvoused via jax.distributed with gloo
collectives — the TPU-native analogue of the reference's documented
DBR behavior (reference ``runner_base.py:48-61``), testable without a
pod (SURVEY.md §4 test strategy).

These tests spawn subprocesses that each import jax (~seconds), so the
gang is kept small.
"""

import numpy as np
import pytest

from sparkdl import HorovodRunner


def _allreduce_main(scale):
    import numpy as np

    import sparkdl_tpu.hvd as hvd

    hvd.init()
    x = np.full((3,), float(hvd.rank() + 1), np.float32) * scale
    total = hvd.allreduce(x, op=hvd.Sum)
    avg = hvd.allreduce(x)
    gathered = hvd.allgather(np.array([[hvd.rank()]], np.int32))
    bcast = hvd.broadcast(np.array([hvd.rank() * 7.0], np.float32), root_rank=1)
    # 0-d tensors must keep their shape (regression: ascontiguousarray
    # silently promoted scalars to (1,), breaking keras Variable.assign
    # on scalar optimizer state like SGD/iteration).
    scalar = hvd.broadcast(np.asarray(np.int32(3 + hvd.rank())), root_rank=0)
    scalar_sum = hvd.allreduce(np.asarray(np.float32(1.0)), op=hvd.Sum)
    # reducescatter: dim0 = size*2; each rank keeps its reduced chunk
    rs_in = np.arange(hvd.size() * 2, dtype=np.float32) + hvd.rank()
    rs = hvd.reducescatter(rs_in, op=hvd.Sum)
    # allgather_object: ragged pickled payloads, rank order preserved
    objs = hvd.allgather_object({"r": hvd.rank(),
                                 "pad": "x" * (hvd.rank() + 1) * 7})
    from sparkdl_tpu.horovod import log_to_driver

    log_to_driver(f"rank {hvd.rank()} done")
    return {
        "objs": [o["r"] for o in objs],
        "rank": hvd.rank(),
        "size": hvd.size(),
        "sum": total.tolist(),
        "avg": avg.tolist(),
        "gathered": gathered.tolist(),
        "bcast": bcast.tolist(),
        "scalar_shapes": [np.shape(scalar), np.shape(scalar_sum)],
        "scalar_bcast": int(np.asarray(scalar)),
        "reducescatter": rs.tolist(),
    }


@pytest.mark.gang
def test_np_minus_two_gang(capfd):
    result = HorovodRunner(np=-2).run(_allreduce_main, scale=1.0)
    # rank 0's return value comes back (runner_base.py:93-95)
    assert result["rank"] == 0
    assert result["size"] == 2
    # sum over ranks of (rank+1): 1+2 = 3
    assert result["sum"] == [3.0, 3.0, 3.0]
    assert result["avg"] == [1.5, 1.5, 1.5]
    assert result["gathered"] == [[0], [1]]
    assert result["objs"] == [0, 1]  # allgather_object, rank order
    assert result["bcast"] == [7.0]  # root_rank=1 contributed 1*7
    assert result["scalar_shapes"] == [(), ()]  # 0-d stays 0-d
    assert result["scalar_bcast"] == 3  # rank 0's value
    # rank 0's chunk of sum_r(arange(4)+r): [0+1, 1+2] over 2 ranks
    assert result["reducescatter"] == [1.0, 3.0]
    out = capfd.readouterr().out
    assert "rank 0 done" in out  # log_to_driver surfaced on the driver
    assert "rank 1 done" in out


@pytest.mark.gang
def test_gang_worker_exception_propagates():
    def bad_main():
        import sparkdl_tpu.hvd as hvd

        hvd.init()
        if hvd.rank() == 1:
            raise ValueError("worker 1 exploded")
        return "ok"

    with pytest.raises(RuntimeError, match="worker 1 exploded"):
        HorovodRunner(np=-2).run(bad_main)


@pytest.mark.gang
def test_fail_fast_when_np_exceeds_slots(monkeypatch):
    monkeypatch.setenv("SPARKDL_TPU_NUM_SLOTS", "2")
    with pytest.raises(RuntimeError, match="fails fast"):
        HorovodRunner(np=64).run(lambda: None)


@pytest.mark.gang
def test_np_positive_cluster_mode_local_slots(monkeypatch):
    """np>0 on a slot-limited host: gang of np workers, one per slot."""
    monkeypatch.setenv("SPARKDL_TPU_NUM_SLOTS", "2")
    result = HorovodRunner(np=2).run(_allreduce_main, scale=2.0)
    assert result["size"] == 2
    assert result["sum"] == [6.0, 6.0, 6.0]


@pytest.mark.gang
def test_fast_fail_when_worker_dies_during_rendezvous(monkeypatch):
    """A worker crashing before READY must abort the gang promptly (not
    after the full start timeout) and surface its traceback."""
    import time

    from sparkdl_tpu.horovod import launcher

    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "bogus-platform")
    monkeypatch.setenv("SPARKDL_TPU_START_TIMEOUT", "300")
    # the driver's slot probe would refuse this platform before any
    # worker starts; this test is about the workers themselves dying
    monkeypatch.setattr(
        launcher, "probe_local_devices",
        lambda platform: launcher.LocalDevices(2, "cpu", None))
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="rendezvous"):
        HorovodRunner(np=-2).run(lambda: None)
    assert time.monotonic() - t0 < 120  # fail-fast, not timeout-bound


@pytest.mark.gang
def test_oversized_log_line_does_not_poison_control_plane(capfd):
    """A >64KB stdout line is truncated sender-side; READY/RESULT still
    flow (regression: mid-JSON truncation used to kill the channel)."""

    def noisy_main():
        import sparkdl_tpu.hvd as hvd

        hvd.init()
        print("A" * 200_000)
        return hvd.size()

    assert HorovodRunner(np=-2, driver_log_verbosity="all").run(noisy_main) == 2


@pytest.mark.gang
def test_alltoall_and_grouped_allreduce():
    def main():
        import numpy as np

        import sparkdl_tpu.hvd as hvd

        hvd.init()
        r, n = hvd.rank(), hvd.size()
        # equal alltoall: rank r sends [r*10+j]*2 to rank j
        x = np.concatenate(
            [np.full((2,), r * 10 + j, np.float32) for j in range(n)]
        )
        eq = hvd.alltoall(x)
        # ragged alltoall: rank r sends j+1 rows of value r*10+j to rank j
        parts = [np.full((j + 1,), r * 10 + j, np.float32) for j in range(n)]
        rag = hvd.alltoall(np.concatenate(parts), splits=[j + 1 for j in range(n)])
        # grouped allreduce: mixed dtypes fused per dtype
        g = hvd.grouped_allreduce(
            [np.ones((3,), np.float32) * (r + 1),
             np.ones((2, 2), np.float64) * (r + 1),
             np.ones((4,), np.float32) * 10 * (r + 1)],
            op=hvd.Sum,
        )
        return {
            "rank": r,
            "eq": eq.tolist(),
            "rag": rag.tolist(),
            "g0": g[0].tolist(), "g1": np.asarray(g[1]).tolist(),
            "g2": g[2].tolist(),
        }

    out = HorovodRunner(np=-2).run(main)
    r = out["rank"]
    assert r == 0
    # rank 0 receives from rank 0: [0*10+0]*2, from rank 1: [1*10+0]*2
    assert out["eq"] == [0.0, 0.0, 10.0, 10.0]
    # ragged: rank 0 gets 1 row from each source: [0*10+0, 1*10+0]
    assert out["rag"] == [0.0, 10.0]
    assert out["g0"] == [3.0, 3.0, 3.0]          # (1+2)
    assert out["g1"] == [[3.0, 3.0], [3.0, 3.0]]
    assert out["g2"] == [30.0, 30.0, 30.0, 30.0]


@pytest.mark.gang
def test_alltoall_rank_divergent_splits():
    """Regression: ranks passing different split patterns (one locally
    uniform, one ragged) must agree on the collective sequence."""

    def main():
        import numpy as np

        import sparkdl_tpu.hvd as hvd

        hvd.init()
        r = hvd.rank()
        # rank 0: [2,2] (locally uniform); rank 1: [1,3] (ragged)
        splits = [2, 2] if r == 0 else [1, 3]
        x = np.arange(sum(splits), dtype=np.float32) + 100 * r
        out = hvd.alltoall(x, splits=splits)
        return out.tolist() if r == 0 else None

    # rank 0 receives rank0's chunk0 ([0,1]) + rank1's chunk0 ([100])
    assert HorovodRunner(np=-2).run(main) == [0.0, 1.0, 100.0]


@pytest.mark.gang
def test_orphaned_workers_exit_when_driver_dies():
    """Regression: SIGKILLing the driver must not leave gang workers
    running (observed: they kept their devices)."""
    import os
    import signal
    import subprocess
    import sys
    import textwrap
    import time

    driver_code = textwrap.dedent("""
        from sparkdl import HorovodRunner

        def main():
            import time

            import sparkdl_tpu.hvd as hvd

            hvd.init()
            time.sleep(300)  # long-running training

        HorovodRunner(np=-2).run(main)
    """)
    env = dict(os.environ, SPARKDL_TPU_WORKER_PLATFORM="cpu")
    driver = subprocess.Popen(
        [sys.executable, "-c", driver_code], env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
    )

    def children_of_driver():
        # Workers are direct children of the driver process — scope to
        # THIS test's gang; a machine-wide pgrep would count (and the
        # cleanup would kill) other sessions' workers.
        out = subprocess.run(
            ["pgrep", "-P", str(driver.pid)],
            capture_output=True, text=True,
        ).stdout.split()
        return [int(p) for p in out]

    def alive(pids):
        live = []
        for p in pids:
            try:
                os.kill(p, 0)
                live.append(p)
            except ProcessLookupError:
                pass
        return live

    try:
        deadline = time.monotonic() + 120
        pids = []
        while len(pids) < 2 and time.monotonic() < deadline:
            pids = children_of_driver()
            time.sleep(0.5)
        assert pids, "gang workers never started"

        driver.send_signal(signal.SIGKILL)  # dies without cleanup
        driver.wait()
        deadline = time.monotonic() + 60
        while alive(pids) and time.monotonic() < deadline:
            time.sleep(1)
        leftover = alive(pids)
        for p in leftover:
            os.kill(p, signal.SIGKILL)  # don't pollute the machine
        assert not leftover, f"orphaned workers survived: {leftover}"
    finally:
        if driver.poll() is None:
            driver.kill()
            driver.wait()


@pytest.mark.gang
def test_np_zero_uses_all_slots(monkeypatch):
    """np=0 (deprecated) resolves to all task slots (reference
    README.md:57-61)."""
    monkeypatch.setenv("SPARKDL_TPU_NUM_SLOTS", "2")

    def main():
        import sparkdl_tpu.hvd as hvd

        hvd.init()
        return hvd.size()

    assert HorovodRunner(np=0).run(main) == 2


@pytest.mark.gang
def test_torch_fp16_compressed_allreduce():
    """Compression.fp16 halves the wire buffer; training still syncs."""

    def main():
        import torch

        import horovod.torch as hvd

        hvd.init()
        torch.manual_seed(99 + hvd.rank())
        model = torch.nn.Linear(4, 1)
        opt = hvd.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.05),
            compression=hvd.Compression.fp16,
        )
        hvd.broadcast_parameters(model.state_dict(), root_rank=0)
        x = torch.full((4, 4), float(hvd.rank() + 1))
        ((model(x) - 1.0) ** 2).mean().backward()
        opt.step()
        import numpy as np

        flat = np.concatenate(
            [p.detach().numpy().ravel() for p in model.parameters()]
        )
        gathered = hvd.allgather(flat[None, :])
        return float(np.abs(gathered[0] - gathered[1]).max())

    # fp16 wire precision: ranks stay in lockstep (identical rounding)
    assert HorovodRunner(np=-2).run(main) == 0.0


@pytest.mark.gang
def test_gang_restart_on_failure(monkeypatch, tmp_path):
    """SPARKDL_TPU_MAX_RESTARTS (legacy alias of
    SPARKDL_TPU_GANG_MAX_RETRIES) relaunches a failed gang (SURVEY.md
    §5.3: relaunch IS the recovery story). The failure is a
    preemption-style SIGKILL: under the supervisor only TRANSIENT
    failures consume the budget — user exceptions are never retried
    (tests/horovod/test_fault_tolerance.py)."""
    monkeypatch.setenv("SPARKDL_TPU_MAX_RESTARTS", "2")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_BASE", "0.1")
    monkeypatch.setenv("SPARKDL_TPU_ABORT_GRACE", "5")
    marker = tmp_path / "attempts"

    def flaky_main(marker_path):
        import os
        import signal

        import sparkdl_tpu.hvd as hvd

        hvd.init()
        if hvd.rank() == 0:
            with open(marker_path, "a") as fh:
                fh.write("x")
            if os.path.getsize(marker_path) < 2:
                os.kill(os.getpid(), signal.SIGKILL)  # "preempted"
        return "recovered"

    result = HorovodRunner(np=-2).run(flaky_main, marker_path=str(marker))
    assert result == "recovered"
    assert marker.read_text() == "xx"  # failed once, succeeded once


@pytest.mark.gang
def test_slot_exhaustion_not_retried(monkeypatch):
    monkeypatch.setenv("SPARKDL_TPU_MAX_RESTARTS", "5")
    monkeypatch.setenv("SPARKDL_TPU_NUM_SLOTS", "1")
    import time

    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="fails fast"):
        HorovodRunner(np=8).run(lambda: None)
    assert time.monotonic() - t0 < 30  # no retry loop


@pytest.mark.gang
def test_local_mode_streams_worker_stdout(capfd):
    """np<0 local mode: training stdout reaches the driver output
    regardless of verbosity (reference README.md:44-47); np>0 cluster
    mode keeps the suppression policy."""

    def chatty():
        import sparkdl_tpu.hvd as hvd

        hvd.init()
        print(f"stdout from rank {hvd.rank()}")
        return hvd.size()

    assert HorovodRunner(np=-2).run(chatty) == 2
    out = capfd.readouterr().out
    assert "stdout from rank 0" in out
    assert "stdout from rank 1" in out


@pytest.mark.gang
def test_gang_checkpoint_rank0_saves(tmp_path):
    """TrainCheckpointer inside a gang: each rank's orbax manager is
    process-local (regression: the default cross-process coordination
    deadlocked — the primary rank waited in a barrier the non-primary
    skipped), rank 0 persists, and restore sees the saved state."""

    def main(ckpt_dir):
        import numpy as np

        import sparkdl_tpu.hvd as hvd
        from sparkdl_tpu.utils.checkpoint import (
            TrainCheckpointer,
            should_save,
        )

        hvd.init()
        total = hvd.allreduce(
            np.float32(hvd.rank() + 1.0), op=hvd.Sum
        )
        ckpt = TrainCheckpointer(ckpt_dir, async_save=True)
        try:
            saved = ckpt.save(1, {"total": np.asarray(total)})
            ckpt.wait_until_finished()  # async write -> durable
            hvd.barrier()               # writers before readers
            restored = ckpt.restore(
                target={"total": np.zeros((), np.float32)}
            )
        finally:
            ckpt.close()
        return {
            "rank": hvd.rank(),
            "saved": bool(saved),
            "should": should_save(),
            "restored": float(restored["total"]),
        }

    result = HorovodRunner(np=-2).run(main, ckpt_dir=str(tmp_path / "ck"))
    assert result["rank"] == 0 and result["saved"] and result["should"]
    assert result["restored"] == 3.0  # 1 + 2
