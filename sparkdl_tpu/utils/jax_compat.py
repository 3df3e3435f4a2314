"""Accessors over JAX objects that the analysis, observe and compile
layers share. The package targets the one JAX the container installs
(``setup.py`` states the floor), so nothing here branches on a version:
what JAX spells in one line (``jax.shard_map``, ``jax.lax.axis_size``,
``pltpu.CompilerParams``, ``jax.make_jaxpr``) callers take from JAX.

- lowering accessors the static analysis builds on (``lower``,
  ``lowered_stablehlo``, ``compiled_hlo``);
- the normalized cost-model accessors ``cost_analysis`` /
  ``memory_analysis`` (None-never-raise — backends differ in what they
  report; :mod:`sparkdl_tpu.observe.perf` turns them into MFU/roofline
  gauges);
- telemetry readers that must never be the thing that initializes a
  backend (``device_memory_stats``, ``live_buffer_bytes``,
  ``profiler_trace``): a gang worker's samplers start before
  ``hvd.init()``, and a device touched ahead of
  ``jax.distributed.initialize()`` breaks the rendezvous — and, on a
  TPU host, takes the chip.
"""


def initialized_jax():
    """The imported ``jax`` module once a backend is up, else None."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    return jax if xla_bridge.backends_are_initialized() else None


def lower(fn, *args, **kwargs):
    """``jax.stages.Lowered`` for ``fn(*args, **kwargs)``: uses the
    function's own ``.lower`` when it is already jitted, else wraps it
    in ``jax.jit`` first."""
    import jax

    if hasattr(fn, "lower"):
        return fn.lower(*args, **kwargs)
    return jax.jit(fn).lower(*args, **kwargs)


def lowered_stablehlo(lowered):
    """Pre-partitioning StableHLO text of a ``Lowered``."""
    return lowered.as_text(dialect="stablehlo")


def compiled_hlo(lowered_or_compiled):
    """Post-SPMD-partitioning optimized HLO text — where collectives
    are concrete ops with replica groups. Accepts a ``Lowered`` (which
    it compiles) or an already-``Compiled``."""
    obj = lowered_or_compiled
    if hasattr(obj, "compile"):
        obj = obj.compile()
    return obj.as_text()


def cost_analysis(executable):
    """Normalized XLA cost model for a ``Lowered`` or ``Compiled``
    (or anything duck-typed with a ``cost_analysis()``): a plain dict
    with whichever of ``flops`` / ``bytes_accessed`` /
    ``transcendentals`` the runtime reports, or **None** — never an
    exception. Executables disagree on the return shape (a dict, or a
    one-element list of dicts; some backends raise
    ``NotImplementedError``), so every consumer goes through this
    normalization. The observe
    layer divides these by step wall time into achieved-FLOPs/s and
    MFU gauges (:mod:`sparkdl_tpu.observe.perf`)."""
    try:
        raw = executable.cost_analysis()
    except Exception:
        return None
    if isinstance(raw, (list, tuple)):
        raw = raw[0] if raw else None
    if not isinstance(raw, dict):
        return None
    out = {}
    for key, norm in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = raw.get(key)
        if isinstance(v, (int, float)) and v >= 0:
            out[norm] = float(v)
    return out or None


def memory_analysis(executable):
    """Normalized compiled-memory stats (``Compiled.memory_analysis``,
    a ``CompiledMemoryStats``): plain dict of the
    ``*_size_in_bytes`` fields, or **None** — never an exception
    (``Lowered`` has no memory analysis; neither do deserialized
    executables on some runtimes)."""
    try:
        raw = executable.memory_analysis()
    except Exception:
        return None
    if raw is None:
        return None
    out = {}
    for key in ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes"):
        v = getattr(raw, key, None) if not isinstance(raw, dict) \
            else raw.get(key)
        if isinstance(v, (int, float)) and v >= 0:
            out[key] = int(v)
    return out or None


def device_memory_stats(device=None):
    """Best-effort accelerator memory gauges for the given (default:
    first local) device, or ``None`` when nothing can be read.

    Returns a plain dict with whichever of ``bytes_in_use`` /
    ``peak_bytes_in_use`` / ``bytes_limit`` the PJRT client reports
    (TPU and GPU clients do; CPU returns None/raises). Reads nothing
    until a backend is up: this is called from the heartbeat thread of
    instrumented workers, which starts ahead of ``hvd.init()``, and a
    telemetry beat must never be the thing that initializes a backend
    — a process that has no backend yet has no device memory to
    report."""
    jax = initialized_jax()
    if jax is None:
        return None
    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if isinstance(stats.get(key), (int, float)):
            out[key] = int(stats[key])
    return out or None


class profiler_trace:
    """Context manager capturing a JAX profiler trace of the enclosed
    region into ``log_dir`` — or doing nothing at all, never raising.

    ``__enter__`` returns the log dir when a trace actually started
    and **None** otherwise (no backend up yet in this process,
    another trace already active, an unwritable dir). Same
    no-backend-init rule as :func:`device_memory_stats`: this runs
    inside the worker-side forensic capture service
    (:mod:`sparkdl_tpu.observe.capture`), and an evidence capture must
    never be the thing that initializes a backend — a process that
    hasn't touched a device has nothing worth profiling."""

    def __init__(self, log_dir):
        self._log_dir = log_dir
        self._started = False
        self._jax = None

    def __enter__(self):
        import os

        jax = initialized_jax()
        if jax is None:
            return None
        try:
            os.makedirs(self._log_dir, exist_ok=True)
            jax.profiler.start_trace(self._log_dir)
        except Exception:
            return None
        self._jax = jax
        self._started = True
        return self._log_dir

    def __exit__(self, exc_type, exc, tb):
        if self._started:
            try:
                self._jax.profiler.stop_trace()
            except Exception:
                pass
        return False


def live_buffer_bytes():
    """Sum of live jax array bytes in this process — the fallback
    memory gauge where ``memory_stats`` is unimplemented (CPU rigs).
    Same no-backend-init rule as :func:`device_memory_stats`."""
    jax = initialized_jax()
    if jax is None:
        return None
    try:
        return sum(
            int(getattr(a, "nbytes", 0) or 0) for a in jax.live_arrays()
        )
    except Exception:
        return None
