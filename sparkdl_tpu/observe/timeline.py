"""Structured event timeline: typed spans and instants with rank/host
attribution, exported as Chrome trace-event JSON.

Events are recorded per process (zero-dep, thread-safe, append-only)
and drained in batches — workers ship them to the driver over the
control plane, where :mod:`sparkdl_tpu.observe.aggregate` merges every
rank into ONE Chrome trace (``timeline.json``) that opens directly in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``, alongside
the per-rank xprof traces from :mod:`sparkdl_tpu.utils.profiler`
(:func:`sparkdl_tpu.observe.span` puts every span into both: here
under its name, there as ``sparkdl.<name>`` on the profiler's clock).

Event shape (Chrome trace-event format, the subset Perfetto renders):

- spans:    ``{"ph": "X", "name", "cat", "ts", "dur", "tid", "args"}``
- instants: ``{"ph": "i", "name", "cat", "ts", "s": "p", "tid", "args"}``

``ts``/``dur`` are integer microseconds. ``ts`` is wall-clock
(``time.time``) so events from different processes on a gang's hosts
merge onto one comparable axis; ``dur`` is measured with the monotonic
``perf_counter`` so spans never go negative under clock slew. ``pid``
is deliberately absent here: the merger assigns one pid lane per rank
(driver = lane 0) with ``process_name`` metadata, which is what makes
the merged trace read as a gang-wide story rather than a pile of OS
pids.
"""

import contextlib
import threading
import time


def _tid():
    # Chrome trace tids are int32-ish; Python thread idents can exceed
    # that on 64-bit Linux. Fold, keeping same-thread stability.
    return threading.get_ident() & 0x7FFFFFFF


class Timeline:
    """Append-only per-process event buffer."""

    def __init__(self, clock=time.time, perf=time.perf_counter):
        self._clock = clock
        self._perf = perf
        self._lock = threading.Lock()
        self._events = []
        # Optional per-event mirror (the flight recorder): called with
        # each completed event OUTSIDE the buffer lock, must not raise.
        self.observer = None

    def _mirror(self, ev):
        obs = self.observer
        if obs is not None:
            try:
                obs(ev)
            except Exception:
                pass  # the mirror must never break recording

    def instant(self, name, cat="", tid=None, **args):
        """Record a point event (``ph: "i"``, process-scoped).

        ``tid`` overrides the recording thread's ident — lifecycles
        that span threads (a serving request crosses an HTTP handler
        and the engine thread) key their events on a logical id (the
        request id) so the tree renders as one track per request."""
        ev = {
            "name": name, "cat": cat or "event", "ph": "i",
            "ts": int(self._clock() * 1e6), "s": "p",
            "tid": _tid() if tid is None else int(tid),
            "args": args,
        }
        with self._lock:
            self._events.append(ev)
        self._mirror(ev)
        return ev

    def complete(self, name, start, dur, cat="", tid=None, **args):
        """Record a complete event (``ph: "X"``) with an EXPLICIT
        wall-clock ``start`` and ``dur`` (both seconds) — for spans
        whose endpoints were measured on different threads, where the
        :meth:`span` context manager cannot wrap the block."""
        ev = {
            "name": name, "cat": cat or "span", "ph": "X",
            "ts": int(start * 1e6), "dur": max(0, int(dur * 1e6)),
            "tid": _tid() if tid is None else int(tid), "args": args,
        }
        with self._lock:
            self._events.append(ev)
        self._mirror(ev)
        return ev

    @contextlib.contextmanager
    def span(self, name, cat="", **args):
        """Record a complete event (``ph: "X"``) around the block."""
        t0 = self._clock()
        p0 = self._perf()
        try:
            yield
        finally:
            ev = {
                "name": name, "cat": cat or "span", "ph": "X",
                "ts": int(t0 * 1e6),
                "dur": max(0, int((self._perf() - p0) * 1e6)),
                "tid": _tid(), "args": args,
            }
            with self._lock:
                self._events.append(ev)
            self._mirror(ev)

    def drain(self):
        """Pop and return all buffered events (the flush unit)."""
        with self._lock:
            events, self._events = self._events, []
        return events

    def __len__(self):
        with self._lock:
            return len(self._events)


def chrome_trace(groups):
    """Build one Chrome trace document from per-process event lists.

    ``groups``: iterable of ``(pid, label, events)`` — one trace
    process lane per logical gang member (the aggregator uses lane 0
    for the driver and lane ``rank + 1`` for each worker rank, labeled
    with rank and host). Events are sorted by ``ts`` so the file reads
    chronologically even before a viewer loads it.
    """
    out = []
    for pid, label, events in groups:
        out.append({
            "name": "process_name", "ph": "M", "pid": int(pid),
            "tid": 0, "ts": 0, "args": {"name": str(label)},
        })
        for ev in events:
            ev = dict(ev)
            ev["pid"] = int(pid)
            out.append(ev)
    # Metadata (ph: M) first, then chronological.
    out.sort(key=lambda e: (e["ph"] != "M", e.get("ts", 0)))
    return {"traceEvents": out, "displayTimeUnit": "ms"}
