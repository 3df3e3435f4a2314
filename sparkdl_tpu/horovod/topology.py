"""Gang topology: rank placement across hosts and TPU pod-slice env.

The reference's slot model spans the cluster — "each process will take
an available task slot ... on the task nodes" (reference
``runner_base.py:44-45``, ``:54-55``) — so a gang is a HOSTS x SLOTS
grid, not a flat local list. This module owns that mapping:

- :func:`parse_hosts` reads an mpirun-style host spec
  (``"host1:4,host2:4"``, the launcher's ``SPARKDL_TPU_HOSTS`` env).
- :class:`Placement` maps global rank -> (host index, local_rank,
  local_size) with hosts filled in order, and derives the per-process
  env a worker needs: the horovod-side LOCAL_* values plus the TPU
  runtime's pod-slice variables (``TPU_PROCESS_BOUNDS``,
  ``TPU_CHIPS_PER_PROCESS_BOUNDS``, ``CLOUD_TPU_TASK_ID``,
  ``TPU_PROCESS_ADDRESSES``) so ``jax.distributed.initialize`` on a
  real v4/v5 pod slice sees one process per chip laid out on the ICI
  mesh.

Single-host gangs (the launcher's default) are the 1-host special case;
the Spark barrier backend derives its Placement from the barrier task
infos instead of an env spec (executors already know their hosts).
"""

import functools
import math
import os

HOSTS_ENV = "SPARKDL_TPU_HOSTS"
TPU_PORT_BASE = 8476  # libtpu's default inter-process port


def parse_hosts(spec):
    """``"h1:4,h2:4"`` -> ``[("h1", 4), ("h2", 4)]``; a bare host means
    one slot. Raises ValueError on malformed entries."""
    hosts = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        host, sep, slots = entry.partition(":")
        if not host:
            raise ValueError(f"empty host in host spec {spec!r}")
        try:
            n = int(slots) if sep else 1
        except ValueError:
            raise ValueError(
                f"bad slot count {slots!r} for host {host!r} in {spec!r}"
            )
        if n < 1:
            raise ValueError(f"host {host!r} has {n} slots in {spec!r}")
        hosts.append((host, n))
    if not hosts:
        raise ValueError(f"no hosts in host spec {spec!r}")
    return hosts


class Placement:
    """Rank layout over ``[(host, slots), ...]``, hosts filled in
    order: rank 0..s0-1 on host 0, the next s1 on host 1, ..."""

    def __init__(self, hosts):
        self.hosts = list(hosts)
        self.total_slots = sum(n for _, n in self.hosts)
        self._host_of = []
        self._local_of = []
        for hi, (_, n) in enumerate(self.hosts):
            for li in range(n):
                self._host_of.append(hi)
                self._local_of.append(li)

    @classmethod
    def from_env(cls, environ=os.environ):
        """Placement from SPARKDL_TPU_HOSTS, or None when unset (the
        single-host default)."""
        spec = environ.get(HOSTS_ENV)
        return cls(parse_hosts(spec)) if spec else None

    @classmethod
    def single_host(cls, slots, host="localhost"):
        return cls([(host, slots)])

    def host_index(self, rank):
        return self._host_of[rank]

    def host(self, rank):
        return self.hosts[self._host_of[rank]][0]

    def local_rank(self, rank):
        return self._local_of[rank]

    def local_size(self, rank):
        return self.hosts[self._host_of[rank]][1]

    def env_for_rank(self, rank, *, tpu=False, chip_bounds=None,
                     ports=None):
        """The per-process env for ``rank``: horovod LOCAL_* values,
        plus TPU pod-slice layout when ``tpu`` (one process per chip;
        process grid = hosts x slots-per-host on the ICI mesh).
        ``chip_bounds``: the chip grid of a single host as its slot
        probe saw it (``(2, 2, 1)`` on a four-chip v5e host); a gang
        that fills the host lays its processes out on that grid.
        ``ports``: one loopback port a rank for a single host's TPU
        runtimes to find one another on, the same list for every rank
        (the launcher picks free ones, so a relaunched gang never
        waits for its predecessor's). Without it — ranks that each
        compute their own env, as Spark barrier tasks do — the ports
        are ``TPU_PORT_BASE + rank``. Either way a host runs one
        multi-rank TPU gang at a time: chips are bound by local rank."""
        if not 0 <= rank < self.total_slots:
            raise ValueError(
                f"rank {rank} outside gang of {self.total_slots}"
            )
        env = {
            "SPARKDL_TPU_LOCAL_RANK": str(self.local_rank(rank)),
            "SPARKDL_TPU_LOCAL_SIZE": str(self.local_size(rank)),
        }
        if tpu and self.total_slots > 1:
            # One task <-> one chip (reference runner_base.py:44-45,
            # GPU -> TPU): restrict each worker to its own chip.
            env["TPU_VISIBLE_CHIPS"] = str(self.local_rank(rank))
            env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
            slots = self.hosts[0][1]
            if len(self.hosts) == 1:
                # Single host, one process per chip: the processes are
                # ONE slice over the host's chips, joined by the TPU
                # runtime over loopback ports. Isolated 1,1,1 runtimes
                # would each report process 0 of 1 — jax.distributed
                # does not stitch TPU runtimes together — and every
                # hvd collective would return its own input.
                grid = (tuple(chip_bounds) if chip_bounds
                        and math.prod(chip_bounds) == slots
                        else (slots, 1, 1))
                ports = ports or [TPU_PORT_BASE + r for r in range(slots)]
                if len(ports) != slots:
                    raise ValueError(
                        f"{len(ports)} ports for a gang of {slots}")
                env.update({
                    "TPU_PROCESS_BOUNDS": ",".join(map(str, grid)),
                    "CLOUD_TPU_TASK_ID": str(rank),
                    "TPU_PROCESS_PORT": str(ports[rank]),
                    "TPU_PROCESS_ADDRESSES": ",".join(
                        f"localhost:{port}" for port in ports),
                })
                return env
            if any(n != slots for _, n in self.hosts):
                raise ValueError(
                    "TPU pod slices need a uniform chips-per-host "
                    f"layout; got {self.hosts}"
                )
            # Pod slice: one process per chip, process grid tiled
            # linearly (hosts-major). Same-host processes get distinct
            # ports (base + local_rank). Larger 2D/3D slice shapes
            # should export TPU_PROCESS_BOUNDS themselves; this linear
            # spec covers the common N-host x M-chip rows.
            n_hosts = len(self.hosts)
            env.update({
                "TPU_PROCESS_BOUNDS": f"{n_hosts * slots},1,1",
                "CLOUD_TPU_TASK_ID": str(rank),
                "TPU_PROCESS_PORT": str(
                    TPU_PORT_BASE + self.local_rank(rank)
                ),
                # Loopback aliases in the spec must be rewritten to a
                # routable address here: a remote rank dialing
                # "localhost" for its driver-host peers connects to
                # ITSELF and the mesh init hangs.
                "TPU_PROCESS_ADDRESSES": ",".join(
                    f"{_addressable(self.host(r))}"
                    f":{TPU_PORT_BASE + self.local_rank(r)}"
                    for r in range(self.total_slots)
                ),
            })
        return env


@functools.lru_cache(maxsize=64)
def _addressable(host):
    """A form of ``host`` that PEER machines can dial: loopback
    aliases become this machine's routable IP; anything else (a DNS
    name, a NIC address) passes through. Cached: the peer list is
    rebuilt per rank, and a multi-NIC driver whose default route
    flaps mid-launch must not hand different ranks different peer
    addresses. If no routable address can be determined at all, the
    alias passes through unchanged — same-host peers still work, and
    remote peers fail with a connect error naming the address rather
    than a raw resolver traceback at env-construction time."""
    if host in ("localhost", "127.0.0.1", "::1"):
        from sparkdl_tpu.horovod.control_plane import routable_host_ip

        try:
            return routable_host_ip()
        except OSError:
            return host
    return host


@functools.lru_cache(maxsize=256)
def is_local_host(host):
    """True when ``host`` names THIS machine: loopback, our hostname /
    fqdn, or an address that resolves onto one of this host's own
    addresses. Used by the launcher to decide local ``Popen`` vs the
    remote-exec transport — a multi-host spec must never silently
    collapse onto one machine.

    Cached: the launcher asks per rank, and repeating blocking DNS
    lookups inside the start-timeout window is waste — worse, a flaky
    resolver answering differently between two calls could wire the
    gang for remote transport yet Popen a rank locally."""
    import socket

    if host in ("localhost", "127.0.0.1", "::1"):
        return True
    names = {socket.gethostname()}
    try:
        names.add(socket.getfqdn())
    except OSError:
        pass
    if host in names:
        return True
    try:
        host_ips = {ai[4][0] for ai in socket.getaddrinfo(host, None)}
    except OSError:
        # Unresolvable names are NOT local: better to fail loudly in
        # the remote transport than to quietly launch locally.
        return False
    if any(ip.startswith("127.") or ip == "::1" for ip in host_ips):
        return True
    local_ips = set()
    for n in names:
        try:
            local_ips |= {ai[4][0] for ai in socket.getaddrinfo(n, None)}
        except OSError:
            pass
    # Hostname resolution alone misses NIC addresses on stock
    # Debian-style /etc/hosts (hostname -> 127.0.1.1): a spec naming
    # this driver by its real IP must still classify as local.
    try:
        from sparkdl_tpu.horovod.control_plane import routable_host_ip

        local_ips.add(routable_host_ip())
    except OSError:
        pass
    return bool(host_ips & local_ips)


def placement_from_task_hosts(host_of_rank):
    """Placement for an ALREADY-SCHEDULED gang (Spark barrier mode):
    ``host_of_rank[r]`` is the host executing rank r. Local ranks are
    assigned by order of appearance within each host, so they are
    stable across the gang regardless of scheduling interleave."""
    seen = {}
    locals_ = []
    for h in host_of_rank:
        locals_.append(seen.get(h, 0))
        seen[h] = locals_[-1] + 1
    p = Placement([(h, n) for h, n in seen.items()])
    # Override the order-derived tables: scheduled gangs may interleave
    # hosts, e.g. ranks [h0, h1, h0, h1].
    p._host_of = [list(seen).index(h) for h in host_of_rank]
    p._local_of = locals_
    return p
