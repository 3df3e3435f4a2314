"""Process-wide Horovod-shim state.

Horovod's model is one rank per process (reference contract: np tasks,
one process per task slot, ``runner_base.py:44-45``). The launcher
(:mod:`sparkdl_tpu.horovod.launcher`) exports rank/size/local_rank and
the ``jax.distributed`` coordinator address via environment variables;
``init()`` here resolves them. In local mode (``np=-1``,
reference ``runner_base.py:103``) the runner enters
:func:`local_mode`, which pins size=1 without any rendezvous.
"""

import contextlib
import os
import threading

COORD_ENV = "SPARKDL_TPU_COORDINATOR"
RANK_ENV = "SPARKDL_TPU_RANK"
SIZE_ENV = "SPARKDL_TPU_SIZE"
LOCAL_RANK_ENV = "SPARKDL_TPU_LOCAL_RANK"
LOCAL_SIZE_ENV = "SPARKDL_TPU_LOCAL_SIZE"
FORCE_PLATFORM_ENV = "SPARKDL_TPU_FORCE_PLATFORM"


class _HvdState:
    def __init__(self):
        self.lock = threading.RLock()
        self.initialized = False
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.jax_distributed = False
        # rank_of_process[p]: the hvd rank of the process the JAX
        # runtime numbers p (multi-process gangs; see init). Like
        # jax_distributed it belongs to the jax.distributed client,
        # which outlives shutdown(): exchanged once, kept after.
        self.rank_of_process = None


_state = _HvdState()


def state():
    return _state


def ensure_jax_platform():
    """Apply the platform the launcher forces
    (``SPARKDL_TPU_WORKER_PLATFORM``, e.g. test rigs that run gangs on
    CPU devices) before any backend initialization, and with it the
    gloo collectives a multi-process CPU gang needs.
    """
    import jax

    forced = os.environ.get(FORCE_PLATFORM_ENV)
    if forced:
        jax.config.update("jax_platforms", forced)
        if forced == "cpu" and int(os.environ.get(SIZE_ENV, "1")) > 1:
            # gloo needs the jax.distributed client, which only a
            # multi-process world initializes — arming it for a
            # single-worker gang (np=1, the elastic shrink floor)
            # would fail CPU backend creation outright.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")


def init():
    """Initialize the shim: resolve rank/size and, in a multi-process
    gang, ensure ``jax.distributed`` is initialized against the
    launcher's coordinator (the TPU-native replacement for Horovod's
    MPI rendezvous, per the north star in BASELINE.json)."""
    import jax

    from sparkdl_tpu import observe

    with _state.lock:
        if _state.initialized:
            return
        # a launch span: for a gang it holds jax.distributed.initialize
        # and the rank exchange (where the runtimes of a TPU slice
        # come up); a repeated init() returned above and records none
        with observe.span("hvd.init", cat="launch"):
            _init_locked(jax)


def _init_locked(jax):
    size = int(os.environ.get(SIZE_ENV, "1"))
    rank = int(os.environ.get(RANK_ENV, "0"))
    _state.local_rank = int(os.environ.get(LOCAL_RANK_ENV, str(rank)))
    _state.local_size = int(os.environ.get(LOCAL_SIZE_ENV, str(size)))
    coord = os.environ.get(COORD_ENV)
    if size > 1 and coord:
        ensure_jax_platform()
        if not _state.jax_distributed:
            from jax._src import distributed as _jd

            if _jd.global_state.client is None:
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=size,
                    process_id=rank,
                )
            _state.jax_distributed = True
        if _state.rank_of_process is None:
            _state.rank_of_process = _exchange_ranks(rank, size)
    _state.rank = rank
    _state.size = size
    _state.initialized = True


def reach_backend():
    """This worker's reach of its chip, as its ``worker.backend``
    launch span: the first question asked of the devices starts the
    backend (JAX reports no backend start of its own), and the span
    says what answered: ``platform``, ``devices`` (the local count) and
    ``kind`` (the first's). One a rank a launch, before READY: a gang's
    ranks reach where the runtime's world is checked
    (:func:`_exchange_ranks`), a single worker straight after
    ``hvd.init()`` (``horovod/_worker.py``). A gang's worker only:
    never the driver's process, never local mode."""
    import jax

    from sparkdl_tpu import observe

    with observe.span("worker.backend", cat="launch") as reach:
        devices = jax.local_devices()
        reach.args.update(platform=devices[0].platform, devices=len(devices),
                          kind=devices[0].device_kind)


def _exchange_ranks(rank, size):
    """``hvd.rank()`` is the rank the launcher gave this process — the
    one its payload, its control-plane connection and its log lines
    carry — and NOT ``jax.process_index()``: a TPU runtime numbers the
    processes of a slice by where their chips sit (on a four-chip v5e
    host the launcher's ranks 0..3 came up as processes 1, 3, 2, 0).
    Every process publishes its rank under its runtime index in the
    ``jax.distributed`` key-value store; the list read back orders the
    ``hvd`` mesh by rank (:mod:`sparkdl_tpu.hvd._collectives`).

    Raises when the runtime's world is not the gang's: runtimes that
    did not join (each reporting itself process 0 of 1) would make
    every collective return its own input — a wrong answer, not an
    error."""
    import jax
    from jax._src import distributed as _jd

    reach_backend()
    processes = jax.process_count()
    if processes != size:
        raise RuntimeError(
            f"rank {rank}: the JAX runtime reports "
            f"{processes} process(es) with "
            f"{len(jax.devices())} device(s) for a gang of {size}; "
            "the workers' runtimes did not join one another")
    client = _jd.global_state.client
    client.key_value_set(
        f"sparkdl_tpu/hvd_rank/{jax.process_index()}", str(rank))
    ranks = [
        int(client.blocking_key_value_get(
            f"sparkdl_tpu/hvd_rank/{p}", 120_000))
        for p in range(size)
    ]
    if sorted(ranks) != list(range(size)):
        raise RuntimeError(
            f"rank {rank}: ranks by runtime process index are {ranks}, "
            f"not a permutation of 0..{size - 1}")
    return ranks


def shutdown():
    with _state.lock:
        _state.initialized = False
        _state.rank = 0
        _state.size = 1
        _state.local_rank = 0
        _state.local_size = 1


def require_initialized():
    if not _state.initialized:
        raise ValueError(
            "Horovod has not been initialized; call hvd.init() first."
        )


@contextlib.contextmanager
def local_mode():
    """Single-process mode used by HorovodRunner(np=-1): hvd.init()
    inside the user's main resolves to rank 0 of 1 without rendezvous
    (parity with the reference's in-process local run,
    ``runner_base.py:97-103``)."""
    with _state.lock:
        prev = (
            _state.initialized, _state.rank, _state.size,
            _state.local_rank, _state.local_size,
        )
        _state.initialized = False
        _state.rank = 0
        _state.size = 1
        _state.local_rank = 0
        _state.local_size = 1
    try:
        yield
    finally:
        with _state.lock:
            (_state.initialized, _state.rank, _state.size,
             _state.local_rank, _state.local_size) = prev
