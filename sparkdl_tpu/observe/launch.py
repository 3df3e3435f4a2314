"""The launch record: the lifecycle spans of a gang launch, kept always.

A launch has a few dozen lifecycle events (slot probe, slot claim,
spawn, each worker's boot, control-plane connect, ``hvd.init``, its
reach of its chip, the rendezvous, the job, and every trace, lowering
and XLA compile or cache load JAX reports) and none of
them is inside a step, so they do not wait for the telemetry latch
(``SPARKDL_TPU_TELEMETRY_DIR``): every span of ``cat="launch"``
recorded through :func:`sparkdl_tpu.observe.span` lands here, in a
bounded list in memory. No thread, no file, no environment variable.

One record per process. In the driver it groups spans by launch (one
``launch_id`` per gang spawn; the supervisor's relaunch is another
launch). The bound (``MAX_EVENTS``) is of one process's part of one
launch, the driver's own and each rank's apart, so it holds a gang of
any size; where a part outgrows it, what repeats goes first (JAX's
reports, oldest first) and what a launch has once a process (the
``gang.*`` and ``worker.*`` spans, ``hvd.init``) goes last. Older
launches go whole, oldest first, once all together pass four such
parts. In a worker nothing opens a launch: spans wait in a buffer of
the same bound until the bootstrap ships them to the driver over the
control plane (a ``LAUNCH`` frame before READY, another before BYE),
where :meth:`LaunchRecord.ingest` gives them the launch's id and the
worker's rank.

A span is a plain dict::

    {"name", "start", "end", "cause", "launch_id", "rank", "args"}

``start``/``end`` are wall-clock seconds (``time.time``: the driver
and its workers share a host or an NTP domain, and the phases are
tenths of seconds to tens of seconds long). A host span inside a
profiler session is on the device's clock too (``observe.span`` enters
``TraceAnnotation``); the launch's phases lie before any session and
JAX reports its own (``jax.trace``, ``jax.lower``, ``xla.compile``)
after the fact, so the wall clock, which a harness's own start and
window stamps share, is what lets a reader cut them at a window.
Traces nest (a ``jit`` inside a ``jit`` reports inside its caller's
interval): the record keeps the outermost, with the count of those
inside it (``nested``), so a job leaves a few spans a program whatever
the model's depth; threads overlap, so readers take unions
(:func:`phases`). ``cause`` is the NAME of
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
    ("chip", "worker.backend", max),
    ("rendezvous", "gang.rendezvous", sum),
)
# what a launch has once a process: the last to go from a part that
# outgrows the bound
_ONCE = {name for _, name, _ in _SUMMARY} | {"gang.ready", "worker.job"}


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
        # launch_id -> {rank, None for this process's own: [span]}
        self._launches = collections.OrderedDict()
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
            self._launches[launch_id] = {None: adopted}
            self._open = launch_id
            self._hold(launch_id, None)
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
                self._launches[self._open][None].append(span)
                self._hold(self._open, None)
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
            if taken and launch_id in self._launches:
                rank = taken[0]["rank"]
                self._launches[launch_id].setdefault(rank, []).extend(taken)
                self._hold(launch_id, rank)

    def _hold(self, launch_id, rank):
        """Hold `rank`'s part of `launch_id` to the bound, and all the
        launches together to four parts."""
        part = self._launches[launch_id][rank]
        over = len(part) - self._max
        if over > 0:
            # what repeats goes first, then by age (the sort is stable)
            gone = {id(s) for s in sorted(
                part, key=lambda s: s["name"] in _ONCE)[:over]}
            part[:] = [s for s in part if id(s) not in gone]
        while len(self._launches) > 1 and self._held() > 4 * self._max:
            self._launches.popitem(last=False)

    def _held(self):
        return sum(len(part) for parts in self._launches.values()
                   for part in parts.values())

    def __len__(self):
        with self._lock:
            return self._held() + len(self._pending)

    def report(self, launch_id=None):
        """The spans of `launch_id` (default: the last launch), with
        those no launch has claimed yet, as copies sorted by start."""
        with self._lock:
            if launch_id is None and self._launches:
                launch_id = next(reversed(self._launches))
            spans = list(self._pending)
            for part in self._launches.get(launch_id, {}).values():
                spans += part
            return sorted((dict(s, args=dict(s["args"])) for s in spans),
                          key=lambda s: (s["start"], s["end"]))


def phases(spans, until=None):
    """``{name: seconds}``: for each name the length of the UNION of
    its spans' intervals (nested and overlapping ones count once), over
    the spans that end by `until`. One process's spans, as a rule."""
    by_name = {}
    for s in spans:
        if until is None or s["end"] <= until + 1e-6:
            by_name.setdefault(s["name"], []).append((s["start"], s["end"]))
    took = {}
    for name, intervals in by_name.items():
        took[name], reach = 0.0, float("-inf")
        for start, end in sorted(intervals):
            took[name] += max(end - max(start, reach), 0.0)
            reach = max(reach, end)
    return took


def _says(name, spans):
    """What `name`'s spans say besides their length: what the slot
    probe read or where its child's seconds went, what answered a
    worker's reach."""
    for args in (s["args"] for s in spans if s["name"] == name):
        if args.get("source") == "devices":
            return f" (devices: {args['chips']} x {args['generation']})"
        if "child_backend_s" in args:
            return (" (child: boot {child_boot_s:.1f} s, import "
                    "{child_import_s:.1f} s, chip {child_backend_s:.1f} s, "
                    "exit {child_exit_s:.1f} s)").format(**args)
        if "kind" in args:
            return f" ({args.get('devices')} x {args['kind']})"
    return ""


def summary_line(spans):
    """``gang ready in 18.0 s: slot probe 8.1 s (child: boot 0.3 s,
    import 2.1 s, chip 4.2 s, exit 1.5 s), spawn 0.0 s, boot 6.2 s,
    ...``: one launch's spans (:meth:`LaunchRecord.report`) up
    to its ``gang.ready``, for the operator's INFO log."""
    if not spans:
        return "gang ready (no launch spans recorded)"
    ready = [s["end"] for s in spans if s["name"] == "gang.ready"]
    end = ready[-1] if ready else max(s["end"] for s in spans)
    took = [phases([s for s in spans if s["rank"] == rank], until=end)
            for rank in {s["rank"] for s in spans}]
    parts = []
    for label, name, combine in _SUMMARY:
        durs = [t[name] for t in took if name in t]
        if durs:
            parts.append(f"{label} {combine(durs):.1f} s"
                         + _says(name, spans))
    total = end - min(s["start"] for s in spans)
    return f"gang ready in {total:.1f} s: " + ", ".join(parts)


def job_line(spans, rank=0):
    """``job setup (rank 0): chip 9.8 s, trace 3.1 s (+179 nested),
    lower 1.2 s, compile 5.4 s (41 programs: 41 from the cache, 0
    compiled), longest jit(step) 8.2 s``: `rank`'s way to its first
    step, as unions (:func:`phases`): its reach of its chip, which
    comes before READY, and what JAX reported of its job after."""
    mine = [s for s in spans if s["rank"] == rank]
    took, parts, by_program = phases(mine), [], {}
    for label, name in (("chip", "worker.backend"), ("trace", "jax.trace"),
                        ("lower", "jax.lower"), ("compile", "xla.compile")):
        named = [s for s in mine if s["name"] == name]
        if not named:
            continue
        parts.append(f"{label} {took[name]:.1f} s")
        nested = sum(s["args"].get("nested", 0) for s in named)
        if nested:
            parts[-1] += f" (+{nested} nested)"
        if name == "xla.compile":
            hits = sum(s["args"].get("cache") == "hit" for s in named)
            parts[-1] += (f" ({len(named)} programs: {hits} from the "
                          f"cache, {len(named) - hits} compiled)")
        for s in named:
            program = s["args"].get("program")
            if program is not None:
                # jax.trace says step, the other two jit(step)
                program = str(program)
                program = program if "(" in program else f"jit({program})"
                by_program[program] = (by_program.get(program, 0.0)
                                       + s["end"] - s["start"])
    if not parts:
        return f"job setup (rank {rank}): no job spans recorded"
    if by_program:
        longest = max(by_program, key=by_program.get)
        parts.append(f"longest {longest} {by_program[longest]:.1f} s")
    return f"job setup (rank {rank}): " + ", ".join(parts)
