"""``sparkdl_tpu.observe``: gang-wide structured metrics + a merged
event timeline riding the control plane.

The package's observability layer (ROADMAP: production scale needs a
signal you can alert on, not log lines). Three pieces:

- :mod:`~sparkdl_tpu.observe.metrics` — per-process registry of
  counters/gauges/histograms with Prometheus-text and JSON exporters;
- :mod:`~sparkdl_tpu.observe.timeline` — typed spans/instants exported
  as Chrome trace-event JSON (opens in Perfetto);
- :mod:`~sparkdl_tpu.observe.aggregate` — driver-side merge of worker
  telemetry into one gang-wide view under ``SPARKDL_TPU_TELEMETRY_DIR``.

This module is the instrumentation facade the rest of the package
calls. **Off by default**: unless ``SPARKDL_TPU_TELEMETRY_DIR`` is set
(latched at first use, like the chaos harness), the metric and
timeline helpers here record nothing behind one cached boolean. The
:class:`~sparkdl_tpu.observe.metrics.Registry` class itself is always
live when instantiated explicitly (the serving frontend's ``/metrics``
endpoint owns one; its request metrics are part of its API, not
gang telemetry).

Two things do not wait for that latch. :func:`span` is the one way
the program marks a host interval, and wherever JAX is already
imported it also enters ``jax.profiler.TraceAnnotation("sparkdl." +
name)``, so the interval shows on the profiler's clock beside the
device's operations whenever a profiler session is open (and costs a
no-op inside JAX when none is). And the lifecycle spans of a gang
launch (``cat="launch"``: a few dozen per job, none inside a step) go
into a bounded in-memory record (:mod:`~sparkdl_tpu.observe.launch`),
read back with :func:`launch_report`.

Worker→driver transport: inside a gang worker, the worker bootstrap
registers the control-plane client as the telemetry *sink*
(:func:`set_sink`) and starts a background flusher
(:func:`start_flusher`) that ships cumulative metric snapshots plus
drained timeline events as ``TELEMETRY`` frames every
``SPARKDL_TPU_TELEMETRY_FLUSH_S`` seconds (default 5) and once more at
exit — low-rate batches on the guaranteed control socket, same
backpressure posture as ``log_to_driver``. The chaos harness calls
:func:`flush` synchronously before an injected kill so the fault
instant reaches the driver even though the process dies by SIGKILL.

See ``docs/observability.rst`` for the metric catalog and env knobs.
"""

import itertools
import os
import socket
import sys
import threading
import time

from sparkdl_tpu.observe import launch as _launch
from sparkdl_tpu.observe.metrics import Registry
from sparkdl_tpu.observe.timeline import Timeline

TELEMETRY_DIR_ENV = "SPARKDL_TPU_TELEMETRY_DIR"
FLUSH_S_ENV = "SPARKDL_TPU_TELEMETRY_FLUSH_S"
DEFAULT_FLUSH_S = 5.0

__all__ = [
    "enabled", "telemetry_dir", "metrics", "timeline",
    "inc", "set_gauge", "observe_value", "span", "host_span",
    "instant", "complete",
    "set_sink", "flush", "start_flusher", "stop_flusher",
    "snapshot_payload", "new_run_dir", "Registry", "Timeline",
    "set_flight_recorder", "launch_record", "launch_report",
    "watch_compiles", "TRACE_PREFIX",
]

# every span's name in the profiler's trace (xprof) starts with this
TRACE_PREFIX = "sparkdl."

# Latched like the chaos harness: gangs ship env at spawn, so one
# check at first call suffices and the disabled path stays a single
# boolean test forever after.
_enabled = None

_registry = Registry()
_timeline = Timeline()
_launches = _launch.LaunchRecord()
_open_spans = threading.local()    # .stack: this thread's open _Span
_compile_watch = None              # JAX's listeners, once
_sink = None                       # callable(payload_dict) or None
_sink_lock = threading.Lock()      # serializes flush() payloads
_flusher = None
_flusher_stop = None
_run_seq = itertools.count()


def enabled():
    """True when telemetry was opted in (``SPARKDL_TPU_TELEMETRY_DIR``
    set). Cached; tests reset via :func:`_reset_for_tests`."""
    global _enabled
    if _enabled is None:
        _enabled = bool(os.environ.get(TELEMETRY_DIR_ENV))
    return _enabled


def telemetry_dir():
    return os.environ.get(TELEMETRY_DIR_ENV) or None


def new_run_dir():
    """A fresh per-launch artifact directory under the telemetry root
    (``run-<driverpid>-<n>``): one gang launch — across all its
    supervised attempts — writes one merged view."""
    d = os.path.join(
        telemetry_dir(), f"run-{os.getpid()}-{next(_run_seq)}"
    )
    os.makedirs(d, exist_ok=True)
    return d


def metrics():
    """This process's global registry (driver or worker side)."""
    return _registry


def timeline():
    """This process's global timeline."""
    return _timeline


# -- recording helpers (no-ops when telemetry is off) -----------------------


def inc(name, value=1, **labels):
    if enabled():
        _registry.counter(name, **labels).inc(value)


def set_gauge(name, value, **labels):
    if enabled():
        _registry.gauge(name, **labels).set(value)


def observe_value(name, value, buckets=None, **labels):
    if enabled():
        _registry.histogram(name, buckets=buckets, **labels).observe(value)


class _NoopSpan:
    """Shared do-nothing context manager: what :func:`span` returns
    with telemetry off in a process that has not imported JAX."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` where JAX is ALREADY imported
    in this process, else None. Never an import of its own: the driver
    stays JAX-free (a driver that touched the TPU would starve its own
    workers)."""
    return getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)


def _stack():
    try:
        return _open_spans.stack
    except AttributeError:
        _open_spans.stack = stack = []
        return stack


def _record(name, cat, start, dur, cause, ident, args):
    """One finished span, to whatever keeps it: launch spans always,
    to the launch record; everything, to the timeline, under the
    telemetry latch."""
    if cat == _launch.CAT:
        _launches.add(name, start, start + dur, cause=cause, **args)
    if enabled():
        links = {k: v for k, v in (("cause", cause), ("ident", ident))
                 if v is not None}
        _timeline.complete(name, start, dur, cat=cat, **args, **links)


class _Span:
    """A recorded span: name, start, end, the span that caused it (the
    enclosing recorded span on this thread unless ``cause=`` names
    one) and the identifier the spans of one launch or one step share
    (``ident=``, inherited from the enclosing span). Lives as long as
    its ``with`` block."""

    __slots__ = ("name", "cat", "cause", "ident", "args",
                 "_annotation", "_wall", "_perf")

    def __init__(self, name, cat, cause, ident, args):
        self.name, self.cat, self.args = name, cat, args
        self.cause, self.ident = cause, ident
        self._annotation = None

    def __enter__(self):
        annotation = _trace_annotation()
        if annotation is not None:
            self._annotation = annotation(TRACE_PREFIX + self.name)
            self._annotation.__enter__()
        stack = _stack()
        if stack:
            if self.cause is None:
                self.cause = stack[-1].name
            if self.ident is None:
                self.ident = stack[-1].ident
        stack.append(self)
        self._wall, self._perf = time.time(), time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._perf
        _stack().remove(self)
        _record(self.name, self.cat, self._wall, dur, self.cause,
                self.ident, self.args)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        return False


def span(name, cat="", cause=None, ident=None, **args):
    """THE way the program marks a host interval: ``with
    observe.span("checkpoint.save", cat="checkpoint", step=3):``.

    On the profiler's clock always: where JAX is already imported the
    block runs under ``TraceAnnotation("sparkdl.<name>")``, whether or
    not telemetry is on. With telemetry off (and ``cat`` not
    ``"launch"``) that annotation is all it does: nothing is recorded
    and nothing outlives the call. With telemetry on the span lands in
    the gang timeline with its cause and identifier (:class:`_Span`);
    ``cat="launch"`` spans land in the launch record regardless."""
    if cat != _launch.CAT and not enabled():
        annotation = _trace_annotation()
        return (_NOOP_SPAN if annotation is None
                else annotation(TRACE_PREFIX + name))
    return _Span(name, cat, cause, ident, args)


def host_span(name, **args):
    """A ``cat="host"`` span: host-side work done on behalf of the
    device program (io_callback/debug-callback bodies, checkpoint
    host snapshots). This is the built-in emitter feeding the
    ``host_callback`` component of the ``observe.perf`` step
    attribution — wrap the Python body of a callback (or any host
    detour inside the step window) and the time lands there instead
    of being misread as compute."""
    return span(name, cat="host", **args)


def _enclosing():
    """Name of the recorded span open on this thread, if any."""
    stack = _stack()
    return stack[-1].name if stack else None


def instant(name, cat="", **args):
    """A point event; of ``cat="launch"`` a zero-length span of the
    launch record too."""
    if cat == _launch.CAT:
        now = time.time()
        _launches.add(name, now, now, cause=_enclosing(), **args)
    if enabled():
        _timeline.instant(name, cat=cat, **args)


def complete(name, start, dur, cat="", tid=None, **args):
    """Record a complete event with explicit wall-clock start and
    duration (seconds) — for blocks whose endpoints the caller already
    timed (a worker's boot, JAX's own report of a compile)."""
    if cat == _launch.CAT:
        _launches.add(name, start, start + dur, cause=_enclosing(), **args)
    if enabled():
        _timeline.complete(name, start, dur, cat=cat, tid=tid, **args)


# -- the launch record (always on) -------------------------------------------


def launch_record():
    """This process's :class:`~sparkdl_tpu.observe.launch.LaunchRecord`."""
    return _launches


def launch_report(launch_id=None):
    """The last launch's spans (or `launch_id`'s) as plain dicts,
    sorted by start: the driver's own (``gang.slot_probe``,
    ``gang.slot_claim``, ``gang.spawn``, ``gang.rendezvous``,
    ``gang.ready``) and, per rank, the workers' (``worker.boot``,
    ``worker.connect``, ``hvd.init``, ``worker.backend``, ``worker.job``,
    ``jax.trace``, ``jax.lower``, ``xla.compile``),
    each with ``launch_id``, ``rank``, ``cause``, ``start``, ``end``.
    Call it after ``HorovodRunner.run()`` returns."""
    return _launches.report(launch_id)


# what JAX reports of a program's way to the chip, by launch span
_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAX_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    _COMPILE: "xla.compile",
}


class _Reported(threading.local):
    cache = None    # the verdict precedes the compile's duration
    open = 0        # traces and lowerings begun and not ended
    nested = 0      # those ended inside the outermost one still open


def watch_compiles():
    """Record what JAX reports of every program in this process as
    launch spans: its tracing (``jax.trace``), its lowering
    (``jax.lower``) and its backend compile, or load from the
    persistent cache (``xla.compile``): when, how long, which program
    (``program``) and, for a compile where JAX says so, whether the
    cache answered (``cache``: ``"hit"`` or ``"miss"``). Of traces and
    lowerings the outermost alone is a span, with the count of those
    inside it (``nested``: a ``jit`` within a ``jit`` lies in its
    caller's interval), so a job leaves a few spans a program at any
    depth of model. Registers three ``jax.monitoring`` listeners,
    once; a no-op returning False where JAX is not imported yet."""
    global _compile_watch
    jax = sys.modules.get("jax")
    if jax is None or _compile_watch is not None:
        return _compile_watch is not None
    verdicts = {"/jax/compilation_cache/cache_hits": "hit",
                "/jax/compilation_cache/cache_misses": "miss"}
    seen = _Reported()

    def on_event(event, **_):
        if event in verdicts:
            seen.cache = verdicts[event]

    def on_enter(event, _start, **_):
        # JAX reports each of the three when it begins too, as a scalar
        if event in _JAX_PHASES and event != _COMPILE:
            seen.open += 1

    def on_span(event, start, end, fun_name=None, **_):
        name = _JAX_PHASES.get(event)
        if name == "xla.compile":
            complete(name, start, end - start, cat=_launch.CAT,
                     program=fun_name, cache=seen.cache)
            seen.cache = None
        elif name is not None:
            seen.open = max(seen.open - 1, 0)
            if seen.open:
                seen.nested += 1
            else:
                complete(name, start, end - start, cat=_launch.CAT,
                         program=fun_name, nested=seen.nested)
                seen.nested = 0

    def on_duration(event, duration, **kw):     # a JAX that gives no span
        now = time.time()
        on_span(event, now - duration, now, **kw)

    watch = [("event", "event", on_event), ("scalar", "scalar", on_enter)]
    if hasattr(jax.monitoring, "register_event_time_span_listener"):
        # JAX's own start and end, not now - duration
        watch.append(("event_time_span", "event_time_span", on_span))
    else:
        watch.append(("event_duration_secs", "event_duration", on_duration))
    for register, _, listener in watch:
        getattr(jax.monitoring, f"register_{register}_listener")(listener)
    _compile_watch = watch
    return True


def _unwatch_compiles():
    global _compile_watch
    if _compile_watch is not None:
        from jax._src import monitoring

        for _, unregister, listener in _compile_watch:
            getattr(monitoring, f"unregister_{unregister}_listener")(listener)
        _compile_watch = None


# -- worker flush machinery --------------------------------------------------


def set_sink(sink):
    """Register where :func:`flush` ships payloads (a gang worker
    passes ``client.send_telemetry``); ``None`` unregisters."""
    global _sink
    _sink = sink


def set_flight_recorder(rec):
    """Mirror every timeline event into ``rec`` (a
    :class:`~sparkdl_tpu.observe.flightrec.FlightRecorder`) so the
    tail of the story survives a SIGKILL between flushes. ``None``
    unregisters (and closes nothing — the caller owns the recorder's
    lifecycle)."""
    _timeline.observer = rec.record if rec is not None else None


def snapshot_payload():
    """One flush unit: host/pid attribution, the cumulative metric
    snapshot, and the timeline events drained since the last flush."""
    return {
        "host": socket.gethostname(),
        "pid": os.getpid(),
        "metrics": _registry.snapshot(),
        "events": _timeline.drain(),
    }


def flush(lock_timeout=5.0):
    """Ship a telemetry payload to the registered sink now. Safe to
    call from any thread (payload assembly + send are serialized so a
    periodic flush and a chaos pre-kill flush cannot interleave);
    no-op without a sink or with telemetry off. The lock acquire is
    BOUNDED: if another flush is wedged mid-send (driver stopped
    draining), give up rather than hang — the worker-exit path calls
    this right after ``stop_flusher``'s join also timed out on that
    same wedged thread, and BYE must still go out."""
    sink = _sink
    if sink is None or not enabled():
        return False
    if not _sink_lock.acquire(timeout=lock_timeout):
        return False
    try:
        payload = snapshot_payload()
        try:
            sink(payload)
        except Exception:
            # Telemetry must never take down the instrumented process;
            # the control-plane client already swallows socket errors,
            # this guards custom sinks.
            return False
    finally:
        _sink_lock.release()
    return True


def start_flusher(interval=None):
    """Background periodic flush (worker side). Idempotent. An
    interval <= 0 disables the periodic flusher entirely (returns
    None) — the exit-time and chaos flushes still fire — rather than
    letting ``wait(0)`` busy-spin TELEMETRY frames at the driver."""
    global _flusher, _flusher_stop
    if _flusher is not None and _flusher.is_alive():
        return _flusher
    if interval is None:
        interval = float(os.environ.get(FLUSH_S_ENV, DEFAULT_FLUSH_S))
    if interval <= 0:
        return None
    _flusher_stop = stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            flush()

    _flusher = threading.Thread(
        target=loop, name="sparkdl-tpu-telemetry-flush", daemon=True
    )
    _flusher.start()
    return _flusher


def stop_flusher():
    global _flusher, _flusher_stop
    if _flusher_stop is not None:
        _flusher_stop.set()
    if _flusher is not None:
        _flusher.join(timeout=5.0)
    _flusher = None
    _flusher_stop = None


def _reset_for_tests():
    """Fresh state: re-latch the enabled flag, empty registry,
    timeline (dropping any flight-recorder mirror) and launch record,
    no sink/flusher, no compile listeners, health counters zeroed."""
    global _enabled, _registry, _timeline, _sink, _launches
    stop_flusher()
    _unwatch_compiles()
    _enabled = None
    _registry = Registry()
    _timeline = Timeline()
    _launches = _launch.LaunchRecord()
    _sink = None
    from sparkdl_tpu.observe import health, mem, perf

    health._reset_for_tests()
    perf._reset_for_tests()
    mem._reset_for_tests()
