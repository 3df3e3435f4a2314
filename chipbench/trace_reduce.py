"""From the profiler's trace (``.xplane.pb``) to numbers: device busy
and idle time, time per XLA module and per operation, collectives that
no compute hides, and the longest idle gaps with the host span that
covers each.

Two steps, so that the arithmetic can be checked without a chip:
:func:`load` turns the file into plain tuples, :func:`reduce_events`
turns tuples into numbers. Times are nanoseconds on the trace's clock.
"""

import contextlib
import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
NO_SPAN = "(no span)"


@contextlib.contextmanager
def profile(trace_dir):
    """Profile the block into `trace_dir` (emptied first) under the
    ``chipbench.window`` span, with the Python tracer off: it would slow
    the very host threads whose gaps the trace is read for."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path):
    """``(devices, spans, layout)``: per chip the events of its
    operations and modules line as ``(name, start_ns, duration_ns)``,
    the harness's own host spans (names that start with ``chipbench.``),
    and a listing of every plane and line with its event count, for a
    reader who has to look at a trace by hand."""
    from jax.profiler import ProfileData

    devices, spans, layout = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        chip = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events]
            layout.append([plane.name, line.name, len(events)])
            if chip and line.name in (OPS_LINE, MODULES_LINE):
                key = "ops" if line.name == OPS_LINE else "modules"
                devices.setdefault(int(chip.group(1)), {})[key] = events
            elif not chip:
                spans += [e for e in events if e[0].startswith(SPAN_PREFIX)]
    return devices, spans, layout


def union(intervals):
    """Sorted, merged ``[start, end)`` intervals."""
    merged = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def subtract(a, b):
    """The parts of the merged intervals `a` that no interval of the
    merged `b` covers."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append([at, end])
    return out


def _length(intervals):
    return sum(end - start for start, end in intervals)


def _clip(events, window):
    lo, hi = window
    return [(name, max(start, lo), min(start + dur, hi))
            for name, start, dur in events
            if start < hi and start + dur > lo]


def _by_name(clipped):
    totals = {}
    for name, start, end in clipped:
        totals[name] = totals.get(name, 0) + end - start
    return totals


def _top(totals, n=10):
    return [[name, ns / 1e9] for name, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def module_name(name):
    """``jit_step(123456789)`` -> ``jit_step``: the id changes from run
    to run, the name does not."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_events(devices, spans, window=None):
    """Numbers of one traced window, averaged over the chips in
    `devices`. `window` is ``(start_ns, end_ns)``; by default the
    harness's ``chipbench.window`` span, and without one the stretch
    from the first device event to the last."""
    if window is None:
        marks = [s for s in spans if s[0] == WINDOW_SPAN]
        if marks:
            window = (marks[0][1], marks[0][1] + marks[0][2])
        else:
            every = [e for d in devices.values() for e in d.get("ops", [])]
            window = (min(e[1] for e in every),
                      max(e[1] + e[2] for e in every))
    lo, hi = window
    n = len(devices)
    busy_ns = exposed_ns = 0
    ops, modules, gaps = {}, {}, {}
    host = [(name, start, start + dur) for name, start, dur in spans
            if name != WINDOW_SPAN]
    for chip in devices.values():
        clipped = _clip(chip.get("ops", []), window)
        busy = union((s, e) for _, s, e in clipped)
        busy_ns += _length(busy)
        collective = union(
            (s, e) for name, s, e in clipped if COLLECTIVE.search(name))
        compute = union(
            (s, e) for name, s, e in clipped if not COLLECTIVE.search(name))
        exposed_ns += _length(subtract(collective, compute))
        for name, ns in _by_name(clipped).items():
            ops[name] = ops.get(name, 0) + ns / n
        for name, ns in _by_name(
                [(module_name(m), s, e) for m, s, e in
                 _clip(chip.get("modules", []), window)]).items():
            modules[name] = modules.get(name, 0) + ns / n
        for start, end in subtract([[lo, hi]], busy):
            mid = (start + end) / 2
            covering = [h for h in host if h[1] <= mid < h[2]]
            label = (min(covering, key=lambda h: h[2] - h[1])[0]
                     if covering else NO_SPAN)
            gaps[label] = gaps.get(label, 0) + (end - start) / n
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "chips": n,
        "ops_s": {k: v / 1e9 for k, v in ops.items()},
        "modules_s": {k: v / 1e9 for k, v in modules.items()},
        "device_ops": _top(ops),
        "device_modules": _top(modules),
        "idle_gaps": _top(gaps),
    }


def idle_pct(reduced):
    """The device's idle share of a reduced window, in percent."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce_dir(trace_dir):
    """The reduction of the newest trace under `trace_dir`, with the
    trace's layout beside it. The raw trace (tens of megabytes) is
    removed: the reduction is what a run keeps."""
    devices, spans, layout = load(newest_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    if not any(d.get("ops") for d in devices.values()):
        raise RuntimeError(
            f"the trace has no {OPS_LINE!r} events on a TPU plane; its "
            f"planes and lines: {layout}")
    reduced = reduce_events(devices, spans)
    reduced["layout"] = layout
    return reduced
