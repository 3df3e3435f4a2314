"""Slot-registry semantics: the contract waits while slots are BUSY and
fails fast only when np exceeds the cluster TOTAL (reference
``runner_base.py:56-58``); slot-discovery failures surface as typed
errors instead of optimistic guesses."""

import os
import threading
import time

import pytest

from sparkdl_tpu.horovod.launcher import (
    SlotExhaustionError,
    SlotProbeError,
    available_slots,
    claim_slots,
)


@pytest.fixture
def slot_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "slots")
    monkeypatch.setenv("SPARKDL_TPU_SLOT_DIR", d)
    return d


def test_claim_and_release_roundtrip(slot_dir):
    c = claim_slots(3, 4, timeout=1)
    c2 = claim_slots(1, 4, timeout=1)  # 3 busy + 1 = exactly total
    c.release()
    c2.release()
    c3 = claim_slots(4, 4, timeout=1)
    c3.release()


def test_busy_slots_block_until_released(slot_dir):
    first = claim_slots(3, 4, timeout=1)
    acquired = []

    def waiter():
        c = claim_slots(2, 4, timeout=10)
        acquired.append(time.monotonic())
        c.release()

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.8)
    assert not acquired, "claim went through while slots were busy"
    released_at = time.monotonic()
    first.release()
    t.join(10)
    assert acquired, "claim never went through after release"
    assert acquired[0] >= released_at


def test_wait_timeout_raises_with_busy_count(slot_dir):
    first = claim_slots(3, 4, timeout=1)
    with pytest.raises(RuntimeError, match="3 busy"):
        claim_slots(2, 4, timeout=0.5)
    first.release()


def test_stale_claims_of_dead_processes_are_reaped(slot_dir):
    import subprocess
    import sys

    # A real pid that is certainly dead by the time we look.
    p = subprocess.Popen([sys.executable, "-c", "pass"])
    p.wait()
    os.makedirs(slot_dir, exist_ok=True)
    with open(os.path.join(slot_dir, "stale.claim"), "w") as f:
        f.write(f"{p.pid} 4")
    # All 4 slots look busy, but the owner is dead: claim must succeed
    # immediately after the reap, not time out.
    c = claim_slots(4, 4, timeout=2)
    c.release()
    assert not os.path.exists(os.path.join(slot_dir, "stale.claim"))


def test_corrupt_claim_files_are_ignored(slot_dir):
    os.makedirs(slot_dir, exist_ok=True)
    with open(os.path.join(slot_dir, "junk.claim"), "w") as f:
        f.write("not a pid")
    c = claim_slots(4, 4, timeout=2)
    c.release()


def test_probe_failure_surfaces_as_typed_error(monkeypatch):
    monkeypatch.delenv("SPARKDL_TPU_NUM_SLOTS", raising=False)
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "bogus-platform")
    with pytest.raises(SlotProbeError, match="without this probe"):
        available_slots()


@pytest.mark.parametrize("platform,hosts,seen,bound", [
    (None, None, "tpu", True),     # a chip host that sets nothing
    ("tpu", None, "tpu", True),
    (None, None, "cpu", False),    # nothing attached: nothing to bind
    ("cpu", None, None, False),    # forced to the CPU: never probed
    (None, "h0:2,h1:2", None, False),  # a hosts spec: not this host's
])
def test_chip_binding_follows_what_is_attached(
        monkeypatch, platform, hosts, seen, bound):
    """One chip a rank wherever the host's probe saw TPU chips — also
    under SPARKDL_TPU_NUM_SLOTS, which gives only the count, and in
    local mode (the caller asks the same question for every mode)."""
    from sparkdl_tpu.horovod import launcher
    from sparkdl_tpu.horovod.topology import Placement, parse_hosts

    monkeypatch.setenv("SPARKDL_TPU_NUM_SLOTS", "4")
    asked = []

    def probe(p):
        asked.append(p)
        return launcher.LocalDevices(4, seen, (2, 2, 1))

    monkeypatch.setattr(launcher, "probe_local_devices", probe)
    spec = Placement(parse_hosts(hosts)) if hosts else None
    bounds, ports = launcher._local_tpu(platform, spec, 4)
    assert asked == ([platform] if seen else [])
    if bound:
        assert bounds == (2, 2, 1) and len(set(ports)) == 4
        # two ranks on the four chips: the runtime starts no such
        # slice (seen on the chip), so it is refused by name
        with pytest.raises(SlotExhaustionError, match="np=4.*or np=1"):
            launcher._local_tpu(platform, spec, 2)
        assert launcher._local_tpu(platform, spec, 1)[0] == (2, 2, 1)
    else:
        assert (bounds, ports) == (None, None)
        assert launcher._local_tpu(platform, spec, 2) == (None, None)


@pytest.mark.gang
def test_gang_waits_for_busy_slots_then_runs(slot_dir, monkeypatch):
    """np <= total but slots busy: the job waits (contract), then runs
    once the competing claim releases."""
    from sparkdl import HorovodRunner

    monkeypatch.setenv("SPARKDL_TPU_NUM_SLOTS", "2")
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    busy = claim_slots(2, 2, timeout=1)
    releaser = threading.Timer(2.0, busy.release)
    t0 = time.monotonic()
    releaser.start()
    try:
        result = HorovodRunner(np=2).run(_size_main)
    finally:
        releaser.cancel()
    assert result == 2
    assert time.monotonic() - t0 >= 2.0, "gang did not wait for the claim"


def _size_main():
    import sparkdl_tpu.hvd as hvd

    hvd.init()
    return hvd.size()
