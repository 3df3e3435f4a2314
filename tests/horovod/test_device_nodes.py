"""The slot probe's read of the host's TPU device nodes, on a fake root
tree: what it counts, where it leaves the question to the child, the
grid only a multi-rank gang needs, and what the launch record says."""

import os
import subprocess
import types

import pytest

from sparkdl_tpu import observe
from sparkdl_tpu.horovod import launcher
from sparkdl_tpu.horovod.launcher import LocalDevices, SlotExhaustionError
from sparkdl_tpu.observe.launch import summary_line

V5E, V5P, V4 = "0x0063", "0x0062", "0x005e"
GOOGLE, OTHER = "0x1ae0", "0x8086"


def _function(directory, device, vendor):
    directory.mkdir(parents=True)
    (directory / "vendor").write_text(vendor + "\n")
    (directory / "device").write_text(device + "\n")


def accel(root, n, device=V5E, vendor=GOOGLE):
    """``/dev/accel<n>`` and the PCI function its sysfs link names."""
    (root / "dev").mkdir(exist_ok=True)
    (root / "dev" / f"accel{n}").touch()
    _function(root / "sys/class/accel" / f"accel{n}" / "device",
              device, vendor)


def vfio(root, group, *devices):
    """``/dev/vfio/<group>`` and the PCI functions of its IOMMU group
    (``(device, vendor)`` each)."""
    (root / "dev/vfio").mkdir(parents=True, exist_ok=True)
    (root / "dev/vfio" / str(group)).touch()
    for i, (device, vendor) in enumerate(devices):
        _function(root / "sys/kernel/iommu_groups" / str(group) / "devices"
                  / f"0000:{group:02x}:00.{i}", device, vendor)


def bus(root, n, device=V5E):
    """A PCI bus that lists `n` TPU chips, whatever nodes there are."""
    for i in range(n):
        _function(root / "sys/bus/pci/devices" / f"0000:{i:02x}:05.0",
                  device, GOOGLE)


@pytest.fixture
def host(tmp_path, monkeypatch):
    """A fake root for the probe's read, a driver environment that
    narrows nothing, and a faked child that reports ``host.child``
    (eight TPU chips on a 2x4 grid) and keeps its calls."""
    monkeypatch.setattr(launcher, "_ROOT", str(tmp_path))
    for k in ("JAX_PLATFORMS",) + launcher._NARROWING_ENV:
        monkeypatch.delenv(k, raising=False)
    fake = types.SimpleNamespace(root=tmp_path, calls=[],
                                 child="8 tpu 2,4,1")

    def run(argv, **_):
        fake.calls.append(argv)
        return subprocess.CompletedProcess(
            argv, 0, stdout=fake.child + "\n", stderr="")

    monkeypatch.setattr(launcher.subprocess, "run", run)
    monkeypatch.delenv(observe.TELEMETRY_DIR_ENV, raising=False)
    observe._reset_for_tests()
    yield fake
    observe._reset_for_tests()


CHILD = LocalDevices(8, "tpu", (2, 4, 1))


@pytest.mark.parametrize("chips, grid", [(1, (1, 1, 1)), (4, (2, 2, 1))])
@pytest.mark.parametrize("platform", [None, "tpu"])
def test_v5e_accel_nodes_answer_without_a_child(host, chips, grid, platform):
    root, calls = host.root, host.calls
    for n in range(chips):
        accel(root, n)
    assert launcher.probe_local_devices(platform) == LocalDevices(
        chips, "tpu", grid)
    assert calls == []


def test_vfio_groups_are_counted_and_the_container_node_is_not(host):
    root, calls = host.root, host.calls
    for group in (11, 12, 13):
        vfio(root, group, (V5E, GOOGLE))
    # a group may hold a bridge of another vendor beside its chip
    vfio(root, 14, ("0x1234", OTHER), (V5E, GOOGLE))
    (root / "dev/vfio/vfio").touch()
    assert launcher.probe_local_devices(None) == LocalDevices(
        4, "tpu", (2, 2, 1))
    assert calls == []


def test_the_nodes_are_counted_not_the_pci_bus(host):
    """A container's sysfs lists every chip of the machine; only the
    node passed through can be opened, and the runtime sees only it."""
    root, calls = host.root, host.calls
    bus(root, 4)
    accel(root, 2)
    assert launcher.probe_local_devices(None) == LocalDevices(
        1, "tpu", (1, 1, 1))
    assert calls == []


def _nodes(root, *devices):
    for n, (device, vendor) in enumerate(devices):
        accel(root, n, device, vendor)


@pytest.mark.parametrize("devices, platform, env", [
    ([(V5E, OTHER)], None, {}),                   # another vendor's node
    ([], None, {}),                               # no nodes
    ([(V5E, GOOGLE), ("0x00ff", GOOGLE)], None, {}),  # an unknown id
    ([(V5E, GOOGLE), (V4, GOOGLE)], None, {}),    # two generations
    ([(V5E, GOOGLE)], "gpu", {}),                 # another platform asked
    ([(V5E, GOOGLE)], None, {"JAX_PLATFORMS": "cpu"}),
    ([(V5E, GOOGLE)], None, {"TPU_VISIBLE_CHIPS": "0"}),
    ([(V5E, GOOGLE)], None, {"TPU_PROCESS_BOUNDS": "1,1,1"}),
    ([(V5E, GOOGLE)], None, {"TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1"}),
], ids=["other-vendor", "no-nodes", "unknown-id", "two-generations",
        "platform-gpu", "jax-platforms-cpu", "visible-chips",
        "process-bounds", "chips-per-process"])
def test_where_the_nodes_do_not_settle_it_the_child_answers(
        host, monkeypatch, devices, platform, env):
    root, calls = host.root, host.calls
    _nodes(root, *devices)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    assert launcher.probe_local_devices(platform) == CHILD
    assert len(calls) == 1
    (probe,) = observe.launch_report()
    assert probe["args"]["source"] == "child"


def test_a_tpu_platform_list_still_reads_the_nodes(host, monkeypatch):
    root, calls = host.root, host.calls
    accel(root, 0)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert launcher.probe_local_devices(None).count == 1
    assert calls == []


def test_a_gang_on_cpu_devices_reads_nothing_and_asks_no_child(host):
    root, calls = host.root, host.calls
    accel(root, 0)
    assert launcher.probe_local_devices("cpu") == LocalDevices(
        os.cpu_count() or 1, "cpu", None)
    assert calls == []
    (probe,) = observe.launch_report()
    assert probe["args"] == {"cached": False}


@pytest.mark.parametrize("device, chips", [(V5P, 4), (V5E, 8)])
def test_an_unseen_grid_is_asked_of_the_child_once_and_only_for_a_gang(
        host, device, chips):
    root, calls = host.root, host.calls
    for n in range(chips):
        accel(root, n, device)
    local = launcher.probe_local_devices(None)
    assert (local.count, local.platform, local.chip_bounds) == (
        chips, "tpu", None)
    # a gang of one needs no grid
    assert launcher._local_tpu(None, None, 1)[0] is None
    # a part of the host is refused without asking
    with pytest.raises(SlotExhaustionError, match=f"np={chips}"):
        launcher._local_tpu(None, None, 2)
    assert calls == []
    # the whole host: the runtime's own grid, asked once
    grid = (4, 1, 1) if chips == 4 else (2, 4, 1)
    host.child = f"{chips} tpu {','.join(map(str, grid))}"
    for _ in range(2):
        bounds, ports = launcher._local_tpu(None, None, chips)
        assert bounds == grid and len(set(ports)) == chips
    assert len(calls) == 1


def test_the_span_says_the_nodes_answered(host):
    root, calls = host.root, host.calls
    accel(root, 0)
    found = launcher.probe_local_devices(None)
    assert launcher.probe_local_devices(None) == found
    first, cached = observe.launch_report()
    assert first["args"] == {"cached": False, "source": "devices",
                             "generation": "v5e", "chips": 1}
    assert cached["args"] == {"cached": True}
    assert first["end"] - first["start"] < 1.0
    assert "slot probe 0.0 s (devices: 1 x v5e)" in summary_line(
        observe.launch_report())
