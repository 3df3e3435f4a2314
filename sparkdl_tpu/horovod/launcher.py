"""Gang launcher: the real implementation of the distributed modes the
reference only documents (``runner_base.py:48-61``).

Responsibilities (each clause cites the contract it implements):

- serialize ``(main, kwargs)`` with cloudpickle and ship to workers
  (reference ``runner_base.py:82-83``); warn on large payloads
  (reference ``runner_base.py:90-91``).
- resolve task slots and fail fast if ``np`` exceeds them (reference
  ``runner_base.py:56-58``); ``np == 0`` uses all slots with a
  deprecation warning (reference ``README.md:57-61``).
- start all workers together — a gang (reference ``runner_base.py:
  54-55``): every worker must rendezvous (READY) within the start
  timeout or the whole gang is killed.
- bind one task to one TPU chip — the TPU replacement for the
  reference's one-GPU-per-slot rule (reference ``runner_base.py:44-45``)
  — via ``TPU_VISIBLE_CHIPS`` when multiple workers share a host.
- route worker logs per ``driver_log_verbosity`` and return rank 0's
  cloudpickled result (reference ``runner_base.py:62-72``, ``:93-95``).

Cluster topology is pluggable: the default backend gang-launches local
processes (one per slot); a Spark barrier-mode backend is selected
automatically when pyspark is importable (see
:mod:`sparkdl_tpu.horovod.spark_backend`).
"""

import collections
import functools
import glob
import logging
import os
import socket
import subprocess
import sys
import tempfile
import time

from sparkdl_tpu.horovod.topology import HOSTS_ENV
from sparkdl_tpu.hvd._state import COORD_ENV

COORD_PORT_ENV = "SPARKDL_TPU_COORDINATOR_PORT"
# Warm-start compilation: when the driver's environment names a
# compile cache (JAX_COMPILATION_CACHE_DIR), every worker env carries
# it (local Popen children inherit it via _worker_env's base_env copy;
# remote ranks ride the forward in _remote_worker_cmd), every
# supervised relaunch re-ships it, and _worker.py turns JAX's
# persistent compile cache on there before backend init. The module is
# import-light (jax only inside functions), so the launcher can take
# its names from their canonical home.
from sparkdl_tpu.parallel.compile import (
    JAX_CACHE_DIR_ENV,
    persistent_cache_dir,
)

logger = logging.getLogger("HorovodRunner")


class SlotExhaustionError(RuntimeError):
    """np cannot be placed on the task slots there are: it exceeds
    their TOTAL (reference runner_base.py:56-58), or it is a part of a
    TPU host's chips that the runtime starts no slice on. Never
    retried — more restarts cannot create slots."""


class SlotProbeError(RuntimeError):
    """Slot discovery itself failed (e.g. the device-count subprocess
    died on a wedged accelerator). Surfaced instead of guessing a count:
    an optimistic guess turns into a misleading "only N slots" error
    at launch time. Never retried — the relaunch loop would just re-run
    the same 120s probe against the same wedged backend. Raised only
    where the probe asked a child: where the host's device nodes
    answered, no runtime was started, and a wedged or busy chip fails
    at the worker's start instead (a ``GangFailure`` of kind
    ``start_failure`` with the rank's log tail)."""


class SlotWaitTimeout(RuntimeError):
    """Gave up waiting for busy slots to free. Never retried — a
    relaunch would silently wait the full period again right after
    telling the user it gave up."""

START_TIMEOUT_ENV = "SPARKDL_TPU_START_TIMEOUT"
REMOTE_SHELL_ENV = "SPARKDL_TPU_REMOTE_SHELL"
REMOTE_PYTHON_ENV = "SPARKDL_TPU_REMOTE_PYTHON"
NUM_SLOTS_ENV = "SPARKDL_TPU_NUM_SLOTS"
WORKER_PLATFORM_ENV = "SPARKDL_TPU_WORKER_PLATFORM"
SLOT_WAIT_TIMEOUT_ENV = "SPARKDL_TPU_SLOT_WAIT_TIMEOUT"
SLOT_DIR_ENV = "SPARKDL_TPU_SLOT_DIR"
DEFAULT_START_TIMEOUT = 300.0
DEFAULT_SLOT_WAIT_TIMEOUT = 600.0
LARGE_PAYLOAD_BYTES = 10 << 20


def _free_ports(n):
    """``n`` distinct loopback ports that are free now (held open
    together, so that no two are the same)."""
    socks = [socket.socket(socket.AF_INET, socket.SOCK_STREAM)
             for _ in range(n)]
    try:
        for s in socks:
            s.bind(("127.0.0.1", 0))
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def _free_port():
    return _free_ports(1)[0]


LocalDevices = collections.namedtuple(
    "LocalDevices", "count platform chip_bounds")

# SPARKDL_TPU_NUM_SLOTS is no way round a probe that fails: it gives
# the count of slots, and what they are is still asked of the host.
_NO_PROBE_HINT = (f"(only {WORKER_PLATFORM_ENV}=cpu, a gang on CPU "
                  "devices, is launched without this probe)")


def probe_local_devices(platform, *, ask_child=False):
    """Count local accelerator devices WITHOUT initializing a backend in
    the driver process (a driver that claims the TPU would starve its
    own workers — the analogue of the reference's driver-has-no-GPU
    assumption, ``runner_base.py:44-45``). On a TPU host the device
    nodes this process can open answer, read from the filesystem
    (:func:`_read_device_nodes`): the count, the platform and, for the
    grids seen on the chip, the chip grid. Elsewhere — no TPU nodes, a
    forced platform, an environment that narrows what the runtime
    would show — and where ``ask_child`` is set, a child starts a
    backend, reports what it saw (count, platform, and the chip grid
    its devices span) and has exited — ``subprocess.run`` reaps it —
    before any worker is spawned. Cached: a host's devices do not
    change under a driver, and a supervised relaunch need not pay the
    child's backend start-up again (failures are not cached).

    Where the nodes answered no runtime was started, so a wedged or
    busy chip is not found here: it fails at the worker's start, a
    ``GangFailure`` of kind ``start_failure`` with the rank's log tail.

    Every call is a ``gang.slot_probe`` launch span, zero-length with
    ``cached=True`` when the cache answered. ``source`` says what
    answered: ``"devices"`` (with ``generation`` and ``chips``) or
    ``"child"`` (with ``chips``, the devices it found); a gang on CPU
    devices asks neither. Where a child ran the span says where its
    seconds went: ``child_boot_s`` (span start to the child's first
    line of Python: fork and interpreter), ``child_import_s``
    (``import jax``), ``child_backend_s`` (``jax.local_devices()``) and
    ``child_exit_s`` (the child's last line to the span's end:
    interpreter teardown and the chip's release)."""
    from sparkdl_tpu import observe

    before, started = _probe_local_devices.cache_info().misses, time.time()
    with observe.span("gang.slot_probe", cat="launch") as probe:
        found, says, child = _probe_local_devices(platform, ask_child)
        probe.args["cached"] = cached = (
            _probe_local_devices.cache_info().misses == before)
        if not cached:
            probe.args.update(says)
            if child:
                first, import_s, backend_s, last = child
                probe.args.update(
                    child_boot_s=first - started,
                    child_import_s=import_s, child_backend_s=backend_s,
                    child_exit_s=time.time() - last)
    return found


# Where the slot probe reads the host's device nodes and sysfs: the
# root of the filesystem on a host, a fake tree in tests.
_ROOT = "/"
_GOOGLE_PCI_VENDOR = "0x1ae0"
# a TPU chip's PCI device id -> its generation (the table of
# jax._src.hardware_utils, which the driver does not import)
_TPU_PCI_IDS = {"0x005e": "v4", "0x0062": "v5p", "0x0063": "v5e",
                "0x006f": "v6e"}
# the chip grids the runtime has shown on v5e hosts (_local_tpu's
# docstring); a multi-rank gang on another host asks a child for its own
_CHIP_GRIDS = {("v5e", 1): (1, 1, 1), ("v5e", 4): (2, 2, 1)}
# set in the driver's environment, each narrows or lays out what the
# workers' runtime would show, and the nodes alone cannot say how
_NARROWING_ENV = ("TPU_VISIBLE_CHIPS", "TPU_PROCESS_BOUNDS",
                  "TPU_CHIPS_PER_PROCESS_BOUNDS")


def _openable(pattern):
    return [n for n in sorted(glob.glob(os.path.join(_ROOT, pattern)))
            if os.access(n, os.R_OK | os.W_OK)]


def _pci_ids(function):
    """``(vendor, device)`` of a PCI function's sysfs directory, or
    None where it has no such files."""
    try:
        ids = []
        for name in ("vendor", "device"):
            with open(os.path.join(function, name)) as f:
                ids.append(f.read().strip())
        return tuple(ids)
    except OSError:
        return None


def _tpu_generations():
    """The generation of each TPU chip behind a device node this
    process can open (None for a Google function the table does not
    know): ``/dev/accelN`` through its sysfs device link,
    ``/dev/vfio/<group>`` through the functions of its IOMMU group.
    The NODES are counted, not the PCI bus: in a container sysfs can
    list every chip of the machine while only the nodes passed through
    can be opened, and the runtime sees only those."""
    functions = [
        os.path.join(_ROOT, "sys/class/accel", os.path.basename(n),
                     "device") for n in _openable("dev/accel[0-9]*")]
    for node in _openable("dev/vfio/*"):
        group = os.path.basename(node)
        if group.isdigit():     # not the container node /dev/vfio/vfio
            functions += sorted(glob.glob(os.path.join(
                _ROOT, "sys/kernel/iommu_groups", group, "devices", "*")))
    return [_TPU_PCI_IDS.get(ids[1]) for ids in map(_pci_ids, functions)
            if ids and ids[0] == _GOOGLE_PCI_VENDOR]


def _read_device_nodes(platform):
    """``(LocalDevices, the span's args)`` from the host's TPU device
    nodes, or None where they do not settle what a worker's runtime
    would show: no TPU node, a generation the table lacks or two
    generations, a platform other than the TPU asked for, or a driver
    environment that picks another platform or narrows the chips."""
    jax_platforms = os.environ.get("JAX_PLATFORMS", "")
    if (platform not in (None, "tpu")
            or not (jax_platforms == "" or jax_platforms.startswith("tpu"))
            or any(k in os.environ for k in _NARROWING_ENV)):
        return None
    generations = _tpu_generations()
    if not generations or None in generations or len(set(generations)) > 1:
        return None
    generation, chips = generations[0], len(generations)
    return (LocalDevices(chips, "tpu", _CHIP_GRIDS.get((generation, chips))),
            {"source": "devices", "generation": generation, "chips": chips})


# the probe child's second line: its own clock at its first line of
# Python, the seconds of `import jax` and of `jax.local_devices()`, its
# clock at this line (an older worker image prints the first line alone)
_CHILD_TIMES = "sparkdl-probe-times"


@functools.lru_cache(maxsize=None)
def _probe_local_devices(platform, ask_child=False):
    """``(LocalDevices, the span's args, child's times or None)``: the
    device nodes' answer where they settle it and no child is asked
    for, else the child's."""
    if platform == "cpu":   # a gang on CPU devices: nothing to ask
        return LocalDevices(os.cpu_count() or 1, "cpu", None), {}, None
    read = None if ask_child else _read_device_nodes(platform)
    if read is not None:
        return read + (None,)
    found, child = _ask_child(platform)
    return found, {"source": "child", "chips": found.count}, child


def _ask_child(platform):
    """``(LocalDevices, child's times or None)`` from a child that
    starts a backend and exits."""
    code = (
        "import time\n"
        "t0 = time.time()\n"
        "import jax\n"
        "t1 = time.time()\n"
        + (f"jax.config.update('jax_platforms', {platform!r})\n" if platform else "")
        + "ds = jax.local_devices()\n"
        "t2 = time.time()\n"
        "cs = [getattr(d, 'coords', None) or (i, 0, 0) "
        "for i, d in enumerate(ds)]\n"
        "print(len(ds), ds[0].platform, "
        "','.join(str(max(c) + 1) for c in zip(*cs)))\n"
        f"print({_CHILD_TIMES!r}, t0, t1 - t0, t2 - t1, time.time())\n"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=120,
        )
        lines, child = out.stdout.strip().splitlines(), None
        if lines[-1].startswith(_CHILD_TIMES):
            child = tuple(float(t) for t in lines.pop().split()[1:])
        count, seen, bounds = lines[-1].split()
        return LocalDevices(
            int(count), seen, tuple(int(b) for b in bounds.split(","))), child
    except subprocess.TimeoutExpired:
        raise SlotProbeError(
            "slot discovery timed out after 120s probing local "
            "accelerator devices — the backend may be wedged "
            f"{_NO_PROBE_HINT}"
        )
    except Exception as e:
        detail = ""
        if isinstance(e, (ValueError, IndexError)) and "out" in locals():
            # Parse failure AFTER the probe ran: its stderr says why.
            detail = f"; probe stderr tail: {out.stderr.strip()[-400:]}"
        raise SlotProbeError(
            f"slot discovery failed ({type(e).__name__}: {e}){detail} "
            f"{_NO_PROBE_HINT}"
        )


def _local_tpu(platform, spec_placement, num_workers):
    """``(chip_bounds, ports)`` for a gang whose ranks each take one
    TPU chip of THIS host, else ``(None, None)``. With no hosts spec
    every rank runs here, and what is attached decides whether a rank
    is bound to a chip — in local mode and under SPARKDL_TPU_NUM_SLOTS
    too, which overrides the COUNT of slots, not what they are. The
    probe is cached: in cluster mode this is the answer
    ``_resolve_num_workers`` already has. Only workers forced onto the
    CPU are not asked about.

    Same-host ranks are ONE slice, and the TPU runtime starts a slice
    only on a whole topology: on a four-chip v5e host four ranks join
    (``2,2,1``) and two (``2,1,1`` over chips 0 and 1) die in the
    runtime's start-up (chip runs, PR 21). So a multi-rank gang fills
    the host or is refused here, by name, before anything is spawned.
    Only such a gang needs the grid: where the device nodes answered
    without one (a host of a kind not seen on the chip), a child is
    asked for it, once."""
    if spec_placement is not None or platform == "cpu":
        return None, None
    local = probe_local_devices(platform)
    if local.platform != "tpu":
        return None, None
    if 1 < num_workers == local.count and local.chip_bounds is None:
        local = probe_local_devices(platform, ask_child=True)
    if 1 < num_workers != local.count:
        grid = (f" (grid {','.join(map(str, local.chip_bounds))})"
                if local.chip_bounds else "")
        raise SlotExhaustionError(
            f"HorovodRunner requested {num_workers} ranks on a host of "
            f"{local.count} TPU chips{grid}: same-host ranks "
            "are one TPU slice, and the runtime starts none on a part "
            f"of the host's chips. Use np={local.count}, one rank a "
            "chip, or np=1, one process over all of them.")
    return local.chip_bounds, _free_ports(num_workers)


def available_slots():
    """Total task slots: override via SPARKDL_TPU_NUM_SLOTS, else the
    number of local accelerator chips (CPU rigs: cores). Raises
    :class:`SlotProbeError` when discovery itself fails."""
    override = os.environ.get(NUM_SLOTS_ENV)
    if override:
        return int(override)
    return probe_local_devices(os.environ.get(WORKER_PLATFORM_ENV)).count


# -- slot registry ----------------------------------------------------------
#
# The contract distinguishes BUSY slots from MISSING slots: a job whose
# np fits the cluster total "will wait until np task slots are available
# to launch the job", and only fails when np exceeds the total
# (reference runner_base.py:56-58). Concurrent gangs on one host
# coordinate through a claim-file registry: each gang atomically claims
# its slot count under an flock'd directory, and claims of dead
# processes are reaped so a crashed driver never leaks slots.


def _slot_dir():
    d = os.environ.get(SLOT_DIR_ENV) or os.path.join(
        tempfile.gettempdir(), "sparkdl-tpu-slots"
    )
    os.makedirs(d, exist_ok=True)
    return d


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else


def _busy_slots_locked(d):
    """Sum live claims in the registry (caller holds the lock); reaps
    claims whose owner process is gone."""
    busy = 0
    for name in os.listdir(d):
        if not name.endswith(".claim"):
            continue
        path = os.path.join(d, name)
        try:
            with open(path) as f:
                pid_s, count_s = f.read().split()
            if _pid_alive(int(pid_s)):
                busy += int(count_s)
            else:
                os.unlink(path)  # stale: owner died without release
        except (OSError, ValueError):
            try:
                os.unlink(path)
            except OSError:
                pass
    return busy


class SlotClaim:
    def __init__(self, path):
        self._path = path

    def release(self):
        try:
            os.unlink(self._path)
        except OSError:
            pass


def claim_slots(n, total, timeout=None):
    """Claim ``n`` of ``total`` host slots, waiting while they are busy.

    Wait-until-available semantics (reference runner_base.py:56-58):
    blocks while other live gangs hold slots, raising only on timeout
    (``SPARKDL_TPU_SLOT_WAIT_TIMEOUT``, default 600s). The total-vs-np
    fail-fast check happens in ``_resolve_num_workers`` before this.
    """
    import fcntl
    import uuid

    if timeout is None:
        timeout = float(
            os.environ.get(SLOT_WAIT_TIMEOUT_ENV, DEFAULT_SLOT_WAIT_TIMEOUT)
        )
    d = _slot_dir()
    lock_path = os.path.join(d, ".lock")
    deadline = time.monotonic() + timeout
    logged_waiting = False
    while True:
        with open(lock_path, "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            busy = _busy_slots_locked(d)
            if total - busy >= n:
                path = os.path.join(d, f"{uuid.uuid4().hex}.claim")
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(f"{os.getpid()} {n}")
                os.replace(tmp, path)
                return SlotClaim(path)
        if time.monotonic() > deadline:
            raise SlotWaitTimeout(
                f"HorovodRunner waited {timeout:.0f}s for {n} of {total} "
                f"task slots ({busy} busy in other jobs) without success; "
                "giving up. Increase "
                f"{SLOT_WAIT_TIMEOUT_ENV} or stop the competing jobs."
            )
        if not logged_waiting:
            logger.info(
                "HorovodRunner: %d/%d task slots busy; waiting for %d "
                "to free up (contract: wait while busy, fail only when "
                "np exceeds the cluster total).", busy, total, n,
            )
            logged_waiting = True
        time.sleep(0.2)


def _resolve_num_workers(np_arg, placement=None):
    """Returns (num_workers, mode, total_slots); total_slots is None in
    local mode (oversubscription allowed, no slot accounting). With a
    hosts spec (``placement``), the cluster total is the spec's
    declared slot count — the slots live on the task NODES (reference
    runner_base.py:44-45), so probing only this machine's chips would
    wrongly fail any np that exceeds the local count. The spec is
    TRUSTED, deliberately: cross-checking its local entry against real
    chips would re-introduce the 120s probe subprocess this path
    exists to avoid, so a spec overstating a host's slots fails at
    device-bind time instead (with that rank's log naming the chip).
    Without a spec, the one local probe here is reused for the slot
    claim — probing again at claim time would double the 120s-budget
    subprocess and open a TOCTOU window where a flaky probe shrinks
    the total below np."""
    if np_arg <= -2:
        # Local mode: spawn -np subprocesses on this host (reference
        # runner_base.py:48-53). No slot check: CPU oversubscription is
        # explicitly allowed there.
        return -np_arg, "local", None
    slots = (placement.total_slots if placement is not None
             else available_slots())
    if np_arg == 0:
        # deprecation warning lives in _launch_gang_once (fires once,
        # before backend dispatch)
        return slots, "cluster", slots
    if np_arg > slots:
        # np exceeds the cluster TOTAL: fail fast, never wait
        # (reference runner_base.py:56-58).
        if placement is not None:
            # NUM_SLOTS_ENV is not consulted on this path — pointing
            # users at it would send them in a circle.
            raise SlotExhaustionError(
                f"HorovodRunner requested np={np_arg} task slots but "
                f"the {HOSTS_ENV} spec declares only {slots} in "
                f"total; the job fails fast rather than wait (add "
                f"hosts/slots to {HOSTS_ENV})."
            )
        raise SlotExhaustionError(
            f"HorovodRunner requested np={np_arg} task slots but the host "
            f"has only {slots} in total; the job fails fast rather than "
            "wait (set SPARKDL_TPU_NUM_SLOTS to override slot discovery)."
        )
    return np_arg, "cluster", slots


def _worker_env(base_env, *, rank, size, coordinator, control_addr,
                control_secret, payload_path, job_dir, platform,
                placement=None, tpu_chip_bounds=None, tpu_ports=None):
    from sparkdl_tpu.horovod.topology import Placement

    env = dict(base_env)
    env.update({
        "SPARKDL_TPU_RANK": str(rank),
        "SPARKDL_TPU_SIZE": str(size),
        "SPARKDL_TPU_COORDINATOR": coordinator,
        "SPARKDL_TPU_CONTROL_ADDR": control_addr,
        # Per-job credential for the control plane: the driver
        # cloudpickle-loads the RESULT frame, so only processes holding
        # this secret may speak to it (env never crosses the network).
        "SPARKDL_TPU_CONTROL_SECRET": control_secret,
        "SPARKDL_TPU_PAYLOAD": payload_path,
        "SPARKDL_TPU_JOB_DIR": job_dir,
    })
    # Topology: SPARKDL_TPU_HOSTS defines a hosts x slots grid
    # (reference runner_base.py:44-45, :54-55 — slots live on task
    # NODES); default is the single-host gang. The hosts-spec path also
    # computes the TPU pod-slice env so externally-placed workers (one
    # per chip across a slice) come up on the ICI mesh.
    if placement is None:
        placement = Placement.from_env(base_env)
    if placement is None:
        placement = Placement.single_host(size)
    # One chip per rank when the operator says the workers are TPU
    # workers — or when this host's slot probe SAW TPU chips
    # (tpu_chip_bounds): a chip host that sets nothing must not let
    # every worker take every chip.
    for k, v in placement.env_for_rank(
            rank, tpu=platform == "tpu" or tpu_chip_bounds is not None,
            chip_bounds=tpu_chip_bounds, ports=tpu_ports).items():
        if (k in ("TPU_PROCESS_BOUNDS", "TPU_CHIPS_PER_PROCESS_BOUNDS")
                and base_env.get(k)):
            # An operator-exported slice layout (e.g. a 2D "2,2,1"
            # grid) overrides the linear default.
            continue
        env[k] = v
    if platform:
        env["SPARKDL_TPU_FORCE_PLATFORM"] = platform
    # The driver's XLA_FLAGS (e.g. a forced 8-device host platform in
    # test rigs) must not leak into workers: each worker is one rank on
    # its own device(s).
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        kept = [
            f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        env["XLA_FLAGS"] = " ".join(kept)
    return env


# -- remote exec transport --------------------------------------------------
#
# A hosts spec naming machines other than this one (reference
# runner_base.py:54-55 — slots live "on the task nodes") launches those
# ranks through a remote shell, mpirun-style: ``ssh <host> env K=V ...
# python -m sparkdl_tpu.horovod._worker`` with the rank's payload piped
# over the connection's stdin (SPARKDL_TPU_PAYLOAD=-). Assumes a
# homogeneous cluster: same python (override SPARKDL_TPU_REMOTE_PYTHON)
# and same package layout (PYTHONPATH is forwarded). There is NO silent
# fallback: if the transport is disabled or unavailable, the launch
# fails with a typed error instead of oversubscribing this host.


class RemoteTransportError(RuntimeError):
    """A multi-host placement cannot be honored: the remote-exec
    transport is disabled or no remote shell is available. Raised
    instead of silently launching every rank locally."""


def _resolve_remote_shell():
    """The remote-exec command tokens (``["ssh", "-o", ...]``), or
    raises. ``SPARKDL_TPU_REMOTE_SHELL`` overrides (a test rig points
    it at a fake ssh; ``none`` disables remote exec entirely)."""
    import shlex
    import shutil

    spec = os.environ.get(REMOTE_SHELL_ENV)
    # empty/whitespace = the common way to "unset" a var: fall through
    # to ssh detection rather than exec-ing the hostname as a program
    if spec is not None and spec.strip():
        if spec.strip().lower() == "none":
            raise RemoteTransportError(
                f"{REMOTE_SHELL_ENV}=none disables remote exec"
            )
        return shlex.split(spec)
    if shutil.which("ssh") is None:
        raise RemoteTransportError(
            "no `ssh` on PATH and no SPARKDL_TPU_REMOTE_SHELL override"
        )
    # BatchMode: a gang launch must fail fast, never sit at a password
    # prompt inside the start timeout.
    return ["ssh", "-o", "BatchMode=yes"]


def _remote_worker_cmd(shell_tokens, host, env, base_env, remote_python):
    """Build the remote launch argv. Only the env DELTA the launcher
    computed (gang wiring, TPU layout) plus PYTHONPATH crosses the
    wire — the rest of this machine's environment is not meaningful on
    the task node. Values are shell-quoted: ssh hands the command line
    to the remote shell."""
    import shlex

    # Forward (a) the whole gang-config namespace — matching on the
    # env DELTA alone silently drops vars whose computed value equals
    # the driver's own env, e.g. an operator-pinned
    # SPARKDL_TPU_COORDINATOR or exported TPU_PROCESS_BOUNDS — and
    # (b) anything else the launcher computed fresh for this rank.
    fwd = {
        k: v for k, v in env.items()
        if (k.startswith(("SPARKDL_TPU_", "TPU_"))
            or k in ("CLOUD_TPU_TASK_ID", JAX_CACHE_DIR_ENV)
            or base_env.get(k) != v)
        and k != "XLA_FLAGS"
    }
    if base_env.get("PYTHONPATH"):
        fwd.setdefault("PYTHONPATH", base_env["PYTHONPATH"])
    # The payload file lives on the driver; the remote worker reads it
    # from stdin (ssh forwards our stdin pipe).
    fwd["SPARKDL_TPU_PAYLOAD"] = "-"
    # The control-plane credential must NEVER ride the command line —
    # argv is world-readable in /proc on both machines (and often
    # logged by sshd) while the control plane listens beyond loopback
    # for exactly these gangs. It rides stdin instead: first line of
    # the boot stream, ahead of the payload.
    fwd["SPARKDL_TPU_CONTROL_SECRET"] = "stdin"
    # The driver's job dir path is meaningless remotely; the worker
    # mkdirs its own copy for the per-rank log.
    return (
        list(shell_tokens)
        + [host, "env"]
        + [f"{k}={shlex.quote(v)}" for k, v in sorted(fwd.items())]
        + [remote_python, "-m", "sparkdl_tpu.horovod._worker"]
    )


def _tail(path, n=40):
    try:
        with open(path, "r", errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def launch_gang(np, main, kwargs, driver_log_verbosity, per_rank_kwargs=None):
    """Launch a gang of workers and return rank 0's result.

    Recovery model (SURVEY.md §5.3): gangs are fail-fast, not elastic —
    the recovery story is supervised relaunch
    (:mod:`sparkdl_tpu.horovod.supervisor`). Set
    ``SPARKDL_TPU_GANG_MAX_RETRIES=N`` (legacy alias
    ``SPARKDL_TPU_MAX_RESTARTS``) to relaunch a gang whose failure
    classifies as *transient* — preemption-style signal deaths,
    rendezvous timeouts, control-plane resets — up to N times under
    exponential backoff (fresh job dir, fresh rendezvous), shipping a
    restart context (attempt number + latest checkpoint step from
    ``SPARKDL_TPU_GANG_RESUME_DIR``) to the relaunched workers.
    *Permanent* failures — user-code exceptions, slot exhaustion, bad
    arguments — are never retried.

    :param per_rank_kwargs: optional list (len = gang size) of dicts
        merged into ``kwargs`` for each rank and serialized into that
        rank's own payload — so rank-private data (e.g. a dataset
        shard) is shipped only to its worker instead of to the whole
        gang.
    """
    from sparkdl_tpu import observe
    from sparkdl_tpu.horovod.supervisor import RetryPolicy, supervise

    # Opt-in pre-flight lint (SPARKDL_TPU_PREFLIGHT_LINT=1): analyze
    # the payload and any registered jitted/lowered train step on the
    # driver and refuse to launch on ERROR findings — BEFORE the
    # supervisor loop, slot claims, payload serialization, or any
    # worker spawn. A graph bug is permanent; retrying it under
    # backoff would burn the whole retry budget on chip-hours.
    from sparkdl_tpu.analysis.preflight import (
        preflight_lint,
        take_comms_reports,
        take_fixit_reports,
    )

    preflight_lint(main, kwargs, per_rank_kwargs=per_rank_kwargs)
    # The pre-flight also priced every registered compiled module's
    # collectives (the static comms budget). Collected here so the
    # telemetry run dir carries comms_report.json next to the measured
    # collective_bytes_total — observe.doctor renders the two side by
    # side (predicted-vs-measured is the analyzer's own e2e gate).
    comms_reports = take_comms_reports()
    # With SPARKDL_TPU_PREFLIGHT_FIX=1 the pre-flight also ran the
    # verified fix engine over every registered callable step (auto-
    # donation et al, each applied fix carrying its four proofs).
    # Drained the same way so the run dir carries fixit_report.json
    # next to comms_report.json — observe.doctor renders the fixit
    # table from it.
    fixit_reports = take_fixit_reports()

    # Opt-in telemetry (SPARKDL_TPU_TELEMETRY_DIR): ONE aggregator per
    # launch_gang call spans every supervised attempt, so a chaos run's
    # kill → classify → backoff → resume lands in one merged timeline.
    # Artifacts are written in the finally — a gang that exhausts its
    # retry budget leaves its telemetry behind for the postmortem.
    telemetry = None
    alert_engine = None
    forensics = None
    if observe.enabled():
        from sparkdl_tpu.observe.aggregate import GangTelemetry

        telemetry = GangTelemetry()
        if comms_reports:
            telemetry.add_comms_reports(comms_reports)
        if fixit_reports:
            telemetry.add_fixit_reports(fixit_reports)
        # Streaming alert engine (ISSUE 14; SPARKDL_TPU_ALERTS): ONE
        # engine spans every supervised attempt, like the telemetry
        # aggregator — an elastic gang that resizes between attempts
        # keeps its alert history while the per-rank state is rebuilt
        # via set_world() per attempt (observe/alerts.py).
        from sparkdl_tpu.observe.alerts import maybe_make_engine

        alert_engine = maybe_make_engine(telemetry)
        # Perf forensics (ISSUE 20): alert-triggered / on-demand
        # capture orchestration + regression_report.json. One manager
        # spans attempts like the alert engine; each attempt rebinds
        # it to its control plane (bind_server). The ON_ALERT knob
        # gates only the alert hook — manual /capturez works on any
        # telemetry-on gang.
        from sparkdl_tpu.observe.forensics import maybe_make_forensics

        forensics = maybe_make_forensics(
            telemetry, alert_engine=alert_engine)
    # Autonomous elasticity (ISSUE 16; SPARKDL_TPU_ELASTIC): the
    # capacity watcher / chip-budget arbiter also spans every attempt.
    # It is consulted by the supervisor for relaunch targets via the
    # module-level active-controller registration, and polled in the
    # monitor loop below for planned (checkpoint-boundary) resizes.
    from sparkdl_tpu.horovod.elastic import (
        maybe_make_controller,
        set_active_controller,
    )

    controller = maybe_make_controller(alerts=alert_engine)
    if controller is not None:
        set_active_controller(controller)
    try:
        return supervise(
            lambda extra_env: _launch_gang_once(
                np, main, kwargs, driver_log_verbosity, per_rank_kwargs,
                extra_env=extra_env, telemetry=telemetry,
                alert_engine=alert_engine, controller=controller,
                forensics=forensics,
            ),
            RetryPolicy.from_env(),
        )
    finally:
        if controller is not None:
            set_active_controller(None)
        if telemetry is not None and alert_engine is not None:
            # The report is attached even when nothing fired: a clean
            # run's alerts.json proves the rules were evaluated (the
            # false-positive guard is auditable).
            try:
                telemetry.add_alert_report(alert_engine.report())
            except Exception:
                logger.warning("alert report attach failed",
                               exc_info=True)
        if telemetry is not None and controller is not None:
            # The elastic decision log — every grow/yield/reclaim with
            # its reason — lands in the run dir's elastic.json.
            try:
                telemetry.add_elastic_report(controller.report())
            except Exception:
                logger.warning("elastic report attach failed",
                               exc_info=True)
        # Guard the dir re-read too: the write must NEVER mask the
        # gang's own result/exception, even if the env vanished
        # mid-run (tests) or the dir is unwritable.
        if telemetry is not None and observe.telemetry_dir():
            try:
                paths = telemetry.write(observe.new_run_dir())
            except Exception as e:
                # Catch-all, deliberately: an unwritable dir OR a
                # malformed frame that slipped past ingest's shape
                # check and only detonates in the merge math must
                # never replace the gang's own result/exception.
                logger.warning("telemetry write under %s failed: %s",
                               observe.telemetry_dir(), e)
            else:
                logger.info("gang telemetry written: %s",
                            ", ".join(sorted(paths.values())))


def _launch_gang_once(np, main, kwargs, driver_log_verbosity,
                      per_rank_kwargs=None, extra_env=None,
                      telemetry=None, alert_engine=None,
                      controller=None, forensics=None):
    from sparkdl_tpu import observe

    if np == 0:
        # warned HERE, once, whichever backend ends up hosting the gang
        logger.warning(
            "HorovodRunner(np=0) is deprecated (reference README.md:"
            "57-61); using all available task slots."
        )
    if per_rank_kwargs is not None and np > 0 and len(per_rank_kwargs) != np:
        raise ValueError(
            f"per_rank_kwargs has {len(per_rank_kwargs)} entries for a "
            f"gang of {np}"
        )

    # Spark barrier-mode backend when a real Spark cluster is attached
    # (reference runner_base.py:54-61: "the 2nd spark job started by
    # HorovodRunner"). Tried BEFORE any local slot resolution: cluster
    # slots live on the EXECUTORS (reference runner_base.py:44-45), so
    # probing the driver machine's chips first would wrongly fail any
    # np that exceeds the driver's own count — a 1-core driver in
    # front of a 64-slot cluster is normal. per_rank_kwargs opts OUT:
    # the caller pre-sharded rank-private payloads for a process gang,
    # and the barrier job would silently drop them (the Spark
    # partition-resident path ships data per-partition instead).
    if np >= 0 and per_rank_kwargs is None:
        try:
            from sparkdl_tpu.horovod.spark_backend import maybe_launch_on_spark
        except ImportError:
            pass
        else:
            spark_result = maybe_launch_on_spark(
                np, main, kwargs, driver_log_verbosity
            )
            if spark_result is not None:
                return spark_result.value

    # One launch of the always-on launch record (observe.launch): this
    # spawn's lifecycle spans, the driver's and (over the control
    # plane) each worker's, share its id. Opened before the first slot
    # probe, closed with the attempt; a supervised relaunch is another.
    launch_id = observe.launch_record().open()
    try:
        return _launch_process_gang(
            np, main, kwargs, driver_log_verbosity, per_rank_kwargs,
            extra_env, telemetry, alert_engine, controller, forensics,
            launch_id)
    finally:
        observe.launch_record().close(launch_id)


def _launch_process_gang(np, main, kwargs, driver_log_verbosity,
                         per_rank_kwargs, extra_env, telemetry,
                         alert_engine, controller, forensics, launch_id):
    """The local process gang of :func:`_launch_gang_once` (every
    backend but Spark's barrier job), as launch `launch_id`."""
    import cloudpickle

    from sparkdl_tpu import observe
    from sparkdl_tpu.horovod.control_plane import ControlPlaneServer
    from sparkdl_tpu.horovod.supervisor import GangFailure
    from sparkdl_tpu.horovod.topology import Placement, is_local_host
    from sparkdl_tpu.observe.launch import job_line, summary_line

    spec_placement = Placement.from_env(os.environ)
    num_workers, mode, total_slots = _resolve_num_workers(np, spec_placement)
    # Elastic relaunch (SPARKDL_TPU_GANG_RELAUNCH_NP): the supervisor
    # cleared this target through the reshard pre-flight and shipped it
    # in the restart context — the relaunched gang is RESIZED to it,
    # not just told about it. Cluster mode re-resolves so slot
    # accounting (and the np-exceeds-total fail-fast) follows the new
    # world; local mode spawns exactly that many subprocesses.
    from sparkdl_tpu.horovod.supervisor import (
        RELAUNCH_NP_ENV,
        record_attempt_world,
    )

    relaunch_np = int((extra_env or {}).get(RELAUNCH_NP_ENV) or 0)
    if relaunch_np and relaunch_np != num_workers:
        if mode == "local":
            num_workers = relaunch_np
        else:
            num_workers, mode, total_slots = _resolve_num_workers(
                relaunch_np, spec_placement)
        logger.info(
            "elastic relaunch: gang world resized to np=%d "
            "(%s mode)", num_workers, mode,
        )
    if per_rank_kwargs is not None and len(per_rank_kwargs) != num_workers:
        raise ValueError(
            f"per_rank_kwargs has {len(per_rank_kwargs)} entries for a "
            f"gang of {num_workers}"
        )
    record_attempt_world(num_workers)
    if controller is not None:
        # World-size transitions (shrink/grow/yield/reclaim) are
        # counted here, where the resolved size of the attempt is
        # known; a consumed resize plan is cleared.
        controller.note_attempt(num_workers)

    # Remote-transport availability is knowable NOW — before the slot
    # claim (which can wait minutes for busy slots) and before any
    # payload serialization. Fail-fast philosophy: a CLUSTER gang
    # whose RANKS land on other machines engages the remote transport
    # or dies here, typed. Silently Popen-ing every rank locally would
    # oversubscribe this host's chips while TPU_PROCESS_ADDRESSES
    # points at machines never contacted. Derived from the launched
    # ranks, not the whole spec: np=4 against "localhost:4,nodeB:4"
    # fills only localhost and needs no transport (and must keep the
    # control plane on loopback). LOCAL mode (np<=-2, "spawn -np
    # subprocesses on this host", reference runner_base.py:48-53) is
    # exempt by definition — a hosts spec there is the topology
    # SIMULATION rig (placement env without placement).
    gang_placement = spec_placement or Placement.single_host(num_workers)
    remote_hosts = [] if mode == "local" else sorted({
        gang_placement.host(r) for r in range(num_workers)
        if not is_local_host(gang_placement.host(r))
    })
    remote_shell = remote_python = None
    if remote_hosts:
        try:
            remote_shell = _resolve_remote_shell()
        except RemoteTransportError as e:
            raise RemoteTransportError(
                f"hosts spec places ranks on remote host(s) "
                f"{remote_hosts}, but remote exec is unavailable "
                f"({e}). Refusing to launch the whole gang on this "
                "host — that would oversubscribe its chips and "
                "point TPU_PROCESS_ADDRESSES at machines that were "
                "never contacted. Fix the transport or the "
                f"{HOSTS_ENV} spec."
            )
        remote_python = os.environ.get(REMOTE_PYTHON_ENV, sys.executable)

    # Cluster gangs on this host share a slot registry: wait while
    # another job's gang holds slots, launch when ours free up
    # (reference runner_base.py:56-58 — waiting is the contract;
    # np > total already failed fast above, using the same probe).
    # The registry tracks THIS machine's chips, so a hosts-spec gang
    # claims only its locally-placed ranks — remote ranks consume
    # remote slots, and claiming them here would starve concurrent
    # local gangs for capacity this job isn't using.
    # Local mode (np<-1) deliberately skips this: oversubscription is
    # allowed there. ONE try/finally owns every resource from here —
    # a leaked claim counts as busy for this driver's whole lifetime.
    # Gang health (same opt-in as telemetry): the detector consumes
    # HEARTBEAT frames on the control plane and declares stall/hang
    # verdicts; the monitor loop below acts on them — stack dumps from
    # stalled ranks, then a kind="hang" failure the supervisor
    # classifies as the transient HANG cause.
    detector = None
    statusz = None
    if telemetry is not None:
        from sparkdl_tpu.observe.health import HangDetector

        detector = HangDetector(num_workers)
        if alert_engine is not None:
            # The engine spans attempts (created in launch_gang); the
            # per-rank baselines/latches are rebuilt for THIS
            # attempt's world size — an elastic gang that shrank or
            # grew must not judge new ranks by a dead rank's history.
            alert_engine.set_world(num_workers, detector=detector)

    slot_claim = None
    if mode == "cluster":
        with observe.span("gang.slot_claim", cat="launch",
                          num_workers=num_workers):
            if spec_placement is not None:
                n_local = sum(
                    1 for r in range(num_workers)
                    if is_local_host(spec_placement.host(r))
                )
                local_total = sum(
                    s for h, s in spec_placement.hosts if is_local_host(h)
                )
                if n_local:
                    slot_claim = claim_slots(n_local, local_total)
            else:
                slot_claim = claim_slots(num_workers, total_slots)
    server = None
    procs = []
    boot_logs = []
    boot_paths = {}  # payload path -> staged secret+payload boot file
    try:
        if telemetry is not None:
            # Start INSIDE the resource-owning try so the finally's
            # close() covers every exit, including a failed spawn —
            # a leaked statusz thread would hold the port against the
            # supervisor's next attempt.
            from sparkdl_tpu.observe.statusz import maybe_start_statusz

            statusz = maybe_start_statusz(
                telemetry, detector=detector, num_workers=num_workers,
                alerts=alert_engine, elastic=controller,
                forensics=forensics)
            if statusz is not None:
                logger.info("statusz live at http://%s/statusz",
                            statusz.address)
        job_dir = tempfile.mkdtemp(prefix="sparkdl-tpu-job-")
        if telemetry is not None:
            # Flight-recorder recovery root: rank rings live in the
            # attempt's job dir, and the merged run dir must include
            # their tails even for ranks SIGKILLed mid-flush.
            telemetry.note_job_dir(job_dir)
        payload_paths = []
        for r in range(num_workers):
            rank_kwargs = dict(kwargs)
            if per_rank_kwargs is not None:
                rank_kwargs.update(per_rank_kwargs[r])
            payload = cloudpickle.dumps((main, rank_kwargs))
            if r == 0 and len(payload) > LARGE_PAYLOAD_BYTES:
                # Contract: pickling a large main slows job start
                # (reference runner_base.py:90-91).
                logger.warning(
                    "Pickled main + kwargs is %.1f MB; large closures make "
                    "HorovodRunner jobs slow to start. Move data loading "
                    "inside main().", len(payload) / 2**20,
                )
            path = os.path.join(job_dir, f"payload-{r}.pkl")
            with open(path, "wb") as f:
                f.write(payload)
            payload_paths.append(path)
            if per_rank_kwargs is None:
                # identical payload for everyone: write once, share
                payload_paths = [path] * num_workers
                break

        # Prebuild the native log transport once on the driver so
        # workers don't each pay (or race) the compile inside the gang
        # start timeout; workers then dlopen the cached .so.
        try:
            from sparkdl_tpu.native import load_ctrl_lib

            load_ctrl_lib()
        except Exception:  # pragma: no cover - never block launch on this
            pass

        # Local subprocess mode streams training stdout/stderr to the
        # driver unconditionally (reference README.md:44-47: "Training
        # stdout and stderr messages go to the notebook cell output");
        # cluster mode honors driver_log_verbosity (runner_base.py:62-72).
        effective_verbosity = (
            "all" if mode == "local" else driver_log_verbosity
        )
        platform = os.environ.get(WORKER_PLATFORM_ENV)
        tpu_chip_bounds, tpu_ports = _local_tpu(
            platform, spec_placement, num_workers)
        server = ControlPlaneServer(
            num_workers,
            verbosity=effective_verbosity,
            log_path=os.path.join(job_dir, "job.log"),
            # Remote workers dial back in: bind beyond loopback and
            # advertise a routable address.
            bind_host="0.0.0.0" if remote_hosts else "127.0.0.1",
            telemetry=telemetry,
            health=detector,
            on_launch_spans=functools.partial(
                observe.launch_record().ingest, launch_id),
        )
        if forensics is not None:
            # PROFILE_REQ frames go out through THIS attempt's control
            # plane; its PROFILE_DONE callback clears the per-rank
            # in-flight latch.
            forensics.bind_server(server)
        # jax.distributed's coordinator lives in RANK 0, so the
        # rendezvous address must name rank 0's host, reachable from
        # every worker. Operators behind NAT/DNS oddities can pin it.
        coordinator = os.environ.get(COORD_ENV)
        if not coordinator:
            host0 = gang_placement.host(0)
            if not remote_hosts:
                # all ranks on this machine (incl. local-mode
                # simulation of multi-host specs): loopback rendezvous
                coordinator = f"127.0.0.1:{_free_port()}"
            elif is_local_host(host0):
                coordinator = (
                    f"{server.address.rsplit(':', 1)[0]}:{_free_port()}")
            else:
                # Can't probe a free port on a remote machine. A FIXED
                # well-known port would collide the moment two gangs'
                # rank 0 land on the same host, so derive the default
                # from this job's unique job_dir — stable for the gang
                # (every rank computes the rendezvous from the same
                # coordinator string), near-unique across jobs.
                # Operators pin it via env when a firewall needs one
                # known port.
                port = os.environ.get(COORD_PORT_ENV)
                if not port:
                    import hashlib

                    digest = hashlib.sha256(
                        job_dir.encode()).digest()
                    port = str(49152 + int.from_bytes(
                        digest[:2], "big") % 16384)
                coordinator = f"{host0}:{port}"

        logger.info(
            "Launching HorovodRunner gang: %d worker(s), mode=%s, job_dir=%s",
            num_workers, mode, job_dir,
        )
        compile_cache = persistent_cache_dir()
        if compile_cache:
            # Relaunches of a preempted gang warm-start from here: the
            # env rides every worker env (and every supervised
            # attempt), so the replacement rank deserializes instead
            # of recompiling.
            logger.info(
                "warm-start compile cache for this gang: %s",
                compile_cache,
            )
        spawn_started = time.time()
        # Autotuned perf profile pre-flight (ISSUE 12): resolve the
        # committed per-device-kind profile and ship its knobs in
        # every worker env, UNDER the operator (an env var already set
        # in the driver's environment is never overridden). Applied
        # here — inside the function the supervisor retries — so every
        # relaunched attempt re-inherits the profile through the same
        # env-forwarding path as the restart context; a degraded or
        # malformed profile applies nothing and says so in the log.
        from sparkdl_tpu.perf.profile import preflight_env

        profile_env = preflight_env(os.environ)
        for r in range(num_workers):
            env = _worker_env(
                os.environ, rank=r, size=num_workers,
                coordinator=coordinator, control_addr=server.address,
                control_secret=server.secret,
                payload_path=payload_paths[r], job_dir=job_dir,
                platform=platform, placement=gang_placement,
                tpu_chip_bounds=tpu_chip_bounds, tpu_ports=tpu_ports,
            )
            for pk, pv in profile_env.items():
                env.setdefault(pk, pv)
            if extra_env:
                # Supervisor restart context (attempt number, resume
                # step) — merged per worker, never into the driver's
                # own os.environ.
                env.update(extra_env)
            # Boot-phase output (before the worker installs its log tee
            # — e.g. import errors) lands in the same per-rank log file
            # via an O_APPEND handle, so nothing is ever lost.
            boot_log = open(
                os.path.join(job_dir, f"rank-{r}.log"), "ab", buffering=0
            )
            boot_logs.append(boot_log)
            host_r = gang_placement.host(r)
            # remote_hosts is [] in local mode (simulation rig): every
            # rank spawns locally no matter what the spec names
            if host_r not in remote_hosts:
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "sparkdl_tpu.horovod._worker"],
                    env=env,
                    stdout=boot_log,
                    stderr=subprocess.STDOUT,
                ))
            else:
                cmd = _remote_worker_cmd(
                    remote_shell, host_r, env, os.environ, remote_python
                )
                # Boot stream: secret line + payload bytes, staged in
                # a driver-local file so the kernel (not this loop)
                # streams it — a PIPE write would block on large
                # payloads until the remote end drains. Staged ONCE
                # per unique payload (a shared payload re-copied per
                # rank would write rank-count × GBs); each rank's open
                # gets its own fd/offset. Unlinked in the finally:
                # job_dir outlives the job for postmortems, the secret
                # must not outlive launch.
                boot_path = boot_paths.get(payload_paths[r])
                if boot_path is None:
                    import shutil

                    boot_path = os.path.join(job_dir, f"boot-{r}.bin")
                    with open(boot_path, "wb") as bf:
                        bf.write(server.secret.encode() + b"\n")
                        with open(payload_paths[r], "rb") as pf:
                            shutil.copyfileobj(pf, bf)
                    boot_paths[payload_paths[r]] = boot_path
                with open(boot_path, "rb") as boot_in:
                    procs.append(subprocess.Popen(
                        cmd,
                        stdin=boot_in,
                        stdout=boot_log,
                        stderr=subprocess.STDOUT,
                    ))

        # first Popen to last: each rank's worker.boot starts inside
        observe.complete(
            "gang.spawn", spawn_started, time.time() - spawn_started,
            cat="launch", num_workers=num_workers, mode=mode,
            job_dir=job_dir, compile_cache=compile_cache or "")

        # The spawned children hold their own fds on the boot streams:
        # unlink the secret-bearing files NOW, before the (possibly
        # hours-long) job runs — the finally's unlink is only the
        # backstop for exceptions inside the spawn loop itself.
        for bp in boot_paths.values():
            try:
                os.unlink(bp)
            except OSError:
                pass
        boot_paths.clear()

        def _fail(reason, exit_codes=None, kind="unknown"):
            excs = server.exceptions
            detail = "\n".join(
                f"--- rank {r} ---\n{tb}" for r, tb in sorted(excs.items())
            )
            if not detail:
                bad = (
                    [r for r, c in enumerate(exit_codes) if c]
                    if exit_codes is not None
                    else range(num_workers)
                )
                detail = "\n".join(
                    f"--- rank {r} log tail ---\n"
                    + _tail(os.path.join(job_dir, f"rank-{r}.log"))
                    for r in bad
                )
            # what rank 0 got through before it failed
            logger.info("%s", job_line(observe.launch_report(launch_id)))
            # GangFailure (a RuntimeError) carries the evidence the
            # supervisor's transient-vs-permanent classifier reads:
            # per-rank exit codes (negative = signal = what preemption
            # looks like) and EXC tracebacks.
            raise GangFailure(
                f"{reason}\n{detail}", kind=kind,
                exit_codes=list(exit_codes or []), exceptions=excs,
            )

        # Gang rendezvous with fail-fast (reference runner_base.py:54-58):
        # abort immediately if any worker dies before READY, not after
        # the full start timeout.
        timeout = float(os.environ.get(START_TIMEOUT_ENV, DEFAULT_START_TIMEOUT))
        deadline = time.monotonic() + timeout
        # The span closes however the loop exits, so an aborted
        # rendezvous still shows its (partial) duration on the gang
        # timeline next to the failure instants.
        with observe.span("gang.rendezvous", cat="launch",
                          num_workers=num_workers):
            while server.ready_count() < num_workers:
                dead = [
                    (r, p.poll()) for r, p in enumerate(procs)
                    if p.poll() is not None and p.poll() != 0
                ]
                if dead:
                    time.sleep(0.5)  # let EXC frames drain
                    _fail(
                        "HorovodRunner gang failed to start: worker(s) "
                        f"{[r for r, _ in dead]} exited during rendezvous "
                        f"(codes {[c for _, c in dead]}). Worker logs: {job_dir}",
                        [p.poll() or 0 for p in procs], kind="start_failure",
                    )
                if time.monotonic() > deadline:
                    _fail(
                        f"HorovodRunner gang failed to start: only "
                        f"{server.ready_count()}/{num_workers} workers reached "
                        f"the rendezvous within {timeout:.0f}s (fail-fast, "
                        f"reference runner_base.py:54-58). Worker logs: {job_dir}",
                        kind="rendezvous_timeout",
                    )
                time.sleep(0.05)
        observe.instant("gang.ready", cat="launch",
                        num_workers=num_workers)
        logger.info("%s", summary_line(observe.launch_report(launch_id)))

        # Monitor the running gang. If one rank dies while others are
        # blocked in a collective (which has no timeout on ICI), give the
        # survivors a grace period, then kill them — a failed gang must
        # not wedge the pod (SURVEY.md §7 hard part #3).
        grace = float(os.environ.get("SPARKDL_TPU_ABORT_GRACE", "30"))
        first_death = None
        while any(p.poll() is None for p in procs):
            codes = [p.poll() for p in procs]
            if alert_engine is not None:
                # Streaming SLO rules over the live telemetry window
                # (throttled internally to its check cadence). Firings
                # land as alert.* instants + gang_alerts_total here;
                # the merged report is attached to the run dir in
                # launch_gang's finally. Perf-rule firings also feed
                # the forensics hook: with SPARKDL_TPU_PROFILE_ON_ALERT
                # set, the offending rank is told to capture a profile
                # window and the baseline-vs-regressed diff lands in
                # regression_report.json.
                fired = alert_engine.poll()
                if forensics is not None and fired:
                    forensics.on_alerts(fired)
            if controller is not None and first_death is None:
                # Elastic tick (throttled internally): capacity watch,
                # debounce, arbiter. A non-None return means a planned
                # resize reached its checkpoint boundary — recycle the
                # gang NOW; the supervisor classifies the typed
                # elastic_resize kind as a zero-budget, zero-backoff
                # relaunch at the controller's target np.
                resize = controller.poll()
                if resize is not None:
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                    for p in procs:
                        p.wait()
                    err = GangFailure(
                        f"elastic resize: {resize['direction']} to "
                        f"np={resize['target_np']} "
                        f"({resize['reason']}); resuming from step "
                        f"{resize.get('resume_step')}",
                        kind="elastic_resize",
                        exit_codes=[p.poll() or 0 for p in procs],
                    )
                    err.elastic_direction = resize["direction"]
                    err.elastic_target = resize["target_np"]
                    raise err
            if detector is not None and first_death is None:
                report = detector.poll()
                for r in report["new_stalled"]:
                    # Diagnose while the evidence is live: the stalled
                    # rank's watchdog thread answers with faulthandler
                    # stacks even though its training thread is wedged.
                    server.request_dump(r, reason="stall")
                if report["hang"]:
                    verdict = report["hang"]
                    stalled = detector.stalled_ranks
                    # Final dump sweep over every rank still holding a
                    # control socket (peers' stacks show WHICH
                    # collective the gang is wedged in), then a
                    # bounded wait for the stalled ranks' answers —
                    # the kill below destroys the evidence.
                    for r in range(num_workers):
                        server.request_dump(r, reason=f"hang:{verdict}")
                    dump_grace = float(os.environ.get(
                        "SPARKDL_TPU_DUMP_GRACE", "10"))
                    dump_deadline = time.monotonic() + dump_grace
                    while time.monotonic() < dump_deadline and not all(
                            server.stack_dumps(r) for r in stalled):
                        time.sleep(0.1)
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                    for p in procs:
                        p.wait()
                    raise GangFailure(
                        "HorovodRunner gang hung: beats continued but "
                        f"no rank made progress for "
                        f"{detector.stall_s:.0f}s "
                        f"(verdict: {verdict}; stalled rank(s) "
                        f"{stalled}).\n{detector.describe()}\n"
                        f"Stack dumps captured from rank(s) "
                        f"{sorted(server.stack_dumps())}. "
                        f"Worker logs: {job_dir}",
                        kind="hang", hang_verdict=verdict,
                        exit_codes=[p.poll() or 0 for p in procs],
                        exceptions=server.exceptions,
                    )
            if any(c not in (None, 0) for c in codes):
                if first_death is None:
                    first_death = time.monotonic()
                elif time.monotonic() - first_death > grace:
                    for p in procs:
                        if p.poll() is None:
                            p.kill()
                    _fail(
                        "HorovodRunner job failed: worker(s) "
                        f"{[r for r, c in enumerate(codes) if c not in (None, 0)]} "
                        f"died; surviving ranks were killed after a "
                        f"{grace:.0f}s grace period to avoid a wedged "
                        f"collective.", [c or 0 for c in codes],
                        kind="worker_death",
                    )
            time.sleep(0.1)
        exit_codes = [p.wait() for p in procs]
        if any(exit_codes):
            _fail(
                f"HorovodRunner job failed (exit codes {exit_codes}).",
                exit_codes, kind="worker_death",
            )

        # Drain the control plane: all workers have exited, so their
        # connections are at EOF — process every buffered frame before
        # returning (no tail-of-job log lines lost).
        server.wait_drained(5.0)
        # the job's last LAUNCH frame is in: rank 0's way to its first
        # step (chip, trace, lower, compile or cache load) in one line
        logger.info("%s", job_line(observe.launch_report(launch_id)))

        result_bytes = None
        deadline = time.monotonic() + 30
        while result_bytes is None and time.monotonic() < deadline:
            result_bytes = server.result_bytes
            if result_bytes is None:
                time.sleep(0.05)
        if result_bytes is None:
            # Workers all exited 0 but the RESULT frame never arrived:
            # a control-plane delivery failure, classified transient
            # (a relaunch re-runs the job and re-ships the result).
            raise GangFailure(
                "HorovodRunner job finished but rank 0 returned no result "
                f"over the control plane. Worker logs: {job_dir}",
                kind="no_result",
            )
        return cloudpickle.loads(result_bytes)
    finally:
        if statusz is not None:
            # Stop serving BEFORE the teardown below: a scrape racing
            # the kill path would read half-dismantled state.
            statusz.close()
        if detector is not None and telemetry is not None:
            # However this attempt ended, its detector state (per-rank
            # last beat/step/collective, any verdicts) goes into the
            # merged health.json — the doctor's primary evidence.
            telemetry.add_health_summary(detector.summary())
        for bp in boot_paths.values():
            # spawned children hold their own fds; the secret-bearing
            # file must not persist in the postmortem-kept job_dir
            try:
                os.unlink(bp)
            except OSError:
                pass
        for p in procs:
            if p.poll() is None:
                p.kill()  # a failed gang must not wedge the pod
        for f in boot_logs:
            try:
                f.close()
            except OSError:
                pass
        if server is not None:
            server.close()
        if slot_claim is not None:
            slot_claim.release()
