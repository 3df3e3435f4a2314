"""Test rig configuration.

Tests run on CPU with a virtual 8-device host platform (the TPU-native
test strategy from SURVEY.md §4: single-process multi-device via
``--xla_force_host_platform_device_count``, true multi-process gangs via
subprocess + jax.distributed with gloo collectives). Must run before any
test initializes a JAX backend. The platform is pinned in JAX's config
as well as by the driver's ``JAX_PLATFORMS=cpu``: the suite is a CPU rig
wherever it runs, and on a host with a chip it must not take it.
"""

import os

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Workers spawned by the gang launcher must also run on CPU.
os.environ.setdefault("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
# Keep gang sizes honest on small CI machines.
os.environ.setdefault("SPARKDL_TPU_START_TIMEOUT", "180")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(autouse=True)
def _cold_slot_probe():
    """The launcher caches its slot probe once a process; no test
    inherits another's answer (or must remember to clear it)."""
    yield
    from sparkdl_tpu.horovod import launcher

    launcher._probe_local_devices.cache_clear()


@pytest.fixture
def telemetry(monkeypatch, tmp_path):
    """``observe`` with telemetry on, and reset around the test: the
    counters that say what a traced call was built with count only
    then."""
    from sparkdl_tpu import observe

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    yield observe
    observe._reset_for_tests()
