"""The launch record: the lifecycle spans of a gang launch, kept always.

A launch has a few dozen lifecycle events (slot probe, slot claim,
spawn, each worker's boot, control-plane connect, ``hvd.init``, the
rendezvous, the job, and every XLA compile or cache load) and none of
them is inside a step, so they do not wait for the telemetry latch
(``SPARKDL_TPU_TELEMETRY_DIR``): every span of ``cat="launch"``
recorded through :func:`sparkdl_tpu.observe.span` lands here, in a
bounded list in memory. No thread, no file, no environment variable.

One record per process. In the driver it groups spans by launch (one
``launch_id`` per gang spawn; the supervisor's relaunch is another
launch) and drops the oldest launch first when it outgrows its bound.
In a worker nothing opens a launch: spans wait in the same bounded
buffer until the bootstrap ships them to the driver over the control
plane (a ``LAUNCH`` frame before READY, another before BYE), where
:meth:`LaunchRecord.ingest` gives them the launch's id and the
worker's rank.

A span is a plain dict::

    {"name", "start", "end", "cause", "launch_id", "rank", "args"}

``start``/``end`` are wall-clock seconds (``time.time``: the driver
and its workers share a host or an NTP domain, and the phases are
tenths of seconds to tens of seconds long); ``cause`` is the NAME of
the span that caused it (the enclosing span on the same thread, or
what the caller named: ``gang.spawn`` causes each ``worker.boot``);
``rank`` is None for the driver's own spans.
"""

import collections
import itertools
import os
import threading
import time

CAT = "launch"
MAX_EVENTS = 400

# what the operator's one line names, in launch order: (label, span
# name, how ranks combine: a gang waits for its slowest rank)
_SUMMARY = (
    ("slot probe", "gang.slot_probe", sum),
    ("slot claim", "gang.slot_claim", sum),
    ("spawn", "gang.spawn", sum),
    ("boot", "worker.boot", max),
    ("connect", "worker.connect", max),
    ("hvd.init", "hvd.init", max),
    ("rendezvous", "gang.rendezvous", sum),
)


def process_start_time():
    """Wall-clock second this process was started, from the kernel's
    own record (``/proc``, 10 ms ticks): a worker's boot span starts
    there, before the interpreter did. None where ``/proc`` has no
    such record."""
    try:
        with open("/proc/self/stat") as f:
            # the command name may hold spaces and parentheses
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.time() - max(age, 0.0)
    except (OSError, ValueError, IndexError):
        return None


class LaunchRecord:
    """Bounded in-memory record of launch spans (module docstring)."""

    def __init__(self, max_events=MAX_EVENTS):
        self._max = int(max_events)
        self._lock = threading.Lock()
        self._launches = collections.OrderedDict()  # launch_id -> [span]
        # spans no launch has claimed yet: a worker's own (shipped by
        # drain), or the driver's before open() (a caller's slot probe)
        self._pending = collections.deque(maxlen=self._max)
        self._open = None
        self._seq = itertools.count()

    def open(self):
        """Start a launch in this process: returns its id. Spans
        recorded since the last launch closed (the slot probe a caller
        makes before ``HorovodRunner.run()``) belong to it."""
        with self._lock:
            launch_id = f"{os.getpid()}-{next(self._seq)}"
            adopted = [dict(s, launch_id=launch_id) for s in self._pending]
            self._pending.clear()
            self._launches[launch_id] = adopted
            self._open = launch_id
            self._trim()
        return launch_id

    def close(self, launch_id):
        with self._lock:
            if self._open == launch_id:
                self._open = None

    def add(self, name, start, end, cause=None, **args):
        """Record one span of this process."""
        span = {"name": name, "start": float(start), "end": float(end),
                "cause": cause, "launch_id": None, "rank": None,
                "args": args}
        with self._lock:
            if self._open is None:
                self._pending.append(span)
            else:
                span["launch_id"] = self._open
                self._launches[self._open].append(span)
                self._trim()
        return span

    def drain(self):
        """Pop the spans no launch has claimed: what a worker ships."""
        with self._lock:
            spans = list(self._pending)
            self._pending.clear()
        return spans

    def ingest(self, launch_id, rank, spans):
        """Take a worker's shipped spans into `launch_id` (driver
        side). A span with no cause inside the worker was caused by
        the spawn. Malformed entries are dropped: a bad frame must not
        cost the launch its result."""
        taken = []
        for s in spans if isinstance(spans, list) else []:
            try:
                taken.append({
                    "name": str(s["name"]), "start": float(s["start"]),
                    "end": float(s["end"]),
                    "cause": s.get("cause") or "gang.spawn",
                    "launch_id": launch_id, "rank": int(rank),
                    "args": dict(s.get("args") or {})})
            except (KeyError, TypeError, ValueError):
                continue
        with self._lock:
            if launch_id in self._launches:
                self._launches[launch_id].extend(taken)
                self._trim()

    def _trim(self):
        # the oldest launch goes first, whole; a single launch that
        # outgrows the bound keeps its newest spans
        def total():
            return sum(len(v) for v in self._launches.values())

        while total() > self._max and len(self._launches) > 1:
            self._launches.popitem(last=False)
        for launch_id, spans in self._launches.items():
            if len(spans) > self._max:
                self._launches[launch_id] = spans[-self._max:]

    def __len__(self):
        with self._lock:
            return (sum(len(v) for v in self._launches.values())
                    + len(self._pending))

    def report(self, launch_id=None):
        """The spans of `launch_id` (default: the last launch), with
        those no launch has claimed yet, as copies sorted by start."""
        with self._lock:
            if launch_id is None and self._launches:
                launch_id = next(reversed(self._launches))
            spans = list(self._launches.get(launch_id, ()))
            spans += self._pending
            return sorted((dict(s, args=dict(s["args"])) for s in spans),
                          key=lambda s: (s["start"], s["end"]))


def summary_line(spans):
    """``gang ready in 18.0 s: slot probe 8.1 s, spawn 0.0 s, boot
    6.2 s, ...``: one launch's spans (:meth:`LaunchRecord.report`) up
    to ``gang.ready`` as a line an operator reads without a tool."""
    if not spans:
        return "gang ready (no launch spans recorded)"
    ready = [s["end"] for s in spans if s["name"] == "gang.ready"]
    end = ready[-1] if ready else max(s["end"] for s in spans)
    parts = []
    for label, name, combine in _SUMMARY:
        durs = [s["end"] - s["start"] for s in spans
                if s["name"] == name and s["end"] <= end + 1e-6]
        if durs:
            parts.append(f"{label} {combine(durs):.1f} s")
    total = end - min(s["start"] for s in spans)
    return f"gang ready in {total:.1f} s: " + ", ".join(parts)
