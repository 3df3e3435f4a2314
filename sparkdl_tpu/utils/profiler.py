"""Per-rank profiling (SURVEY.md §5.1 — absent in the reference, where
the only observability is log-based; here every worker can capture a
JAX profiler trace viewable in TensorBoard/Perfetto/xprof).

Enable for a whole HorovodRunner job by exporting
``SPARKDL_TPU_PROFILE=/path/to/dir`` on the driver: each worker writes
``<dir>/rank-<r>`` (wired in the worker bootstrap). Or use
:func:`trace` directly around any region.
"""

import contextlib
import os

PROFILE_ENV = "SPARKDL_TPU_PROFILE"


@contextlib.contextmanager
def trace(log_dir):
    """Capture a JAX profiler trace of the enclosed region."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


@contextlib.contextmanager
def maybe_trace_worker(rank):
    """Trace this worker if the job was launched with profiling on."""
    base = os.environ.get(PROFILE_ENV)
    if not base:
        yield None
        return
    with trace(os.path.join(base, f"rank-{rank}")) as d:
        yield d


def annotate(name):
    """A named region: :func:`sparkdl_tpu.observe.span` with
    ``cat="xprof"``. It shows as ``sparkdl.<name>`` in the xprof trace
    captured by :func:`trace`/:func:`maybe_trace_worker` and, with
    telemetry on, as ``<name>`` in the merged gang timeline
    (``docs/observability.rst``)."""
    from sparkdl_tpu import observe

    return observe.span(name, cat="xprof")
