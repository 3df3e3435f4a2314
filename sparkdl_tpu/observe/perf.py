"""``observe.perf``: step-time attribution, roofline/MFU accounting,
and the perf-regression ledger the compare gate reads.

ROADMAP item 3 says perf claims must be *measured, not asserted* — but
until this module the telemetry stack could only show raw
compile/execute histograms. Three pieces close the gap:

1. **Attribution** (:func:`attribution_report`): derive a per-step
   wall-time breakdown — ``compute`` / ``collective`` /
   ``host_callback`` / ``data_wait`` / ``checkpoint`` — from the spans
   the timeline already carries (``instrument_step`` step spans, the
   ``@_observed`` collective spans, checkpoint save/restore spans, the
   input pipeline's ``data.wait``). Pure interval arithmetic over
   drained/merged events: no jax, artifact-only, so the same math runs
   driver-side at :meth:`GangTelemetry.write` (→ ``perf.json``) and in
   ``observe.doctor`` on a laptop.

   The **overlap-efficiency** metric is the before/after number for
   the async-collective work: a collective span recorded on the step
   thread *blocks* it (serialized); one recorded on another thread
   while the step thread is not inside any instrumented wait is
   *overlapped with compute*. ``overlap_efficiency = overlapped
   collective time / total collective time`` — 0.0 for barrier-style
   ops on the step thread, > 0 once collectives ride
   ``hvd.allreduce_async``'s dispatch thread under compute (the ISSUE
   10 overlap arc; ``tests/observe/test_overlap_gang.py`` pins the
   ring-attention step above zero).
   Component seconds are *step-thread wall time*, so they sum to the
   step span's duration by construction (overlapped collective time is
   concurrent and reported separately).

2. **Roofline/MFU accounting**: :func:`register_step_cost` stores one
   executable's FLOPs/bytes (from the
   :func:`~sparkdl_tpu.utils.jax_compat.cost_analysis` /
   :func:`~sparkdl_tpu.utils.jax_compat.memory_analysis` shims —
   ``None`` on runtimes without a cost model, never an error) and
   :func:`note_step` divides them by each executed step's wall time
   into ``achieved_flops_per_sec`` / ``achieved_bytes_per_sec``
   gauges, plus ``mfu`` and ``membw_util`` against ONE per-device-kind
   peak table (:data:`PEAK_TABLE` — v4/v5e/v5p plus a cpu proxy
   constant, both env-overridable). ``step_operational_intensity`` vs
   the device's ridge point says which roofline wall you are on.
   Everything is behind the PR-3 zero-overhead latch.

3. **Regression ledger** (:func:`history_record` /
   :func:`append_history`): every bench run appends one
   schema-versioned JSON line — git sha, host fingerprint, device
   kind, metrics with optional rep samples — to
   ``benchmarks/results/history.jsonl``, the file
   ``python -m sparkdl_tpu.observe.compare`` diffs with noise-aware
   thresholds. The ledger is the memory the CI perf gate enforces
   against; see :mod:`sparkdl_tpu.observe.compare`.

The single source of truth for chip peaks (the old per-file
``PEAK_FLOPS = 197e12`` copies assumed v5e forever): the MFU gauges
and ``analysis.comms`` read :func:`peak_flops` and its siblings keyed
off the *probed* device kind. (The on-chip benchmark keeps its own
table, ``chipbench/peaks.json``.)
"""

import json
import os
import socket
import subprocess
import sys
import time

PEAK_FLOPS_ENV = "SPARKDL_TPU_PEAK_FLOPS"
PEAK_BYTES_ENV = "SPARKDL_TPU_PEAK_BYTES_PER_S"
PEAK_ICI_ENV = "SPARKDL_TPU_PEAK_ICI_BYTES_PER_S"
HBM_BYTES_ENV = "SPARKDL_TPU_HBM_BYTES"
HISTORY_ENV = "SPARKDL_TPU_PERF_HISTORY"

BREAKDOWN_SCHEMA = "sparkdl_tpu.perf.breakdown/1"
HISTORY_SCHEMA = 1

# Wall-time categories the attribution understands, in render order.
# ``compute`` is the remainder of the step span not covered by any
# instrumented wait on the step thread.
COMPONENTS = ("compute", "collective", "host_callback", "data_wait",
              "checkpoint")

# timeline span cat -> breakdown component
_CAT_TO_COMPONENT = {
    "collective": "collective",
    "host": "host_callback",
    "data": "data_wait",
    "checkpoint": "checkpoint",
}

# Dense bf16 peak FLOPs/s, HBM bytes/s, and aggregate ICI
# (inter-chip interconnect) bytes/s per chip, keyed by the normalized
# device kind (public TPU specs; ICI row = total off-chip link
# bandwidth per chip, the denominator the static comms budget divides
# wire bytes by). The ``cpu`` entry is a nominal proxy constant — a
# deviceless dev container has no honest peak, but the CPU-proxy
# trajectory still wants a stable denominator so its MFU-shaped gauge
# moves only when the code does. Override any axis with
# SPARKDL_TPU_PEAK_FLOPS / SPARKDL_TPU_PEAK_BYTES_PER_S /
# SPARKDL_TPU_PEAK_ICI_BYTES_PER_S.
PEAK_TABLE = {
    "v4": (275e12, 1.23e12, 3.0e11),    # 2400 Gbps ICI
    "v5e": (197e12, 0.82e12, 2.0e11),   # 1600 Gbps ICI
    "v5p": (459e12, 2.77e12, 6.0e11),   # 4800 Gbps ICI
    # Nominal many-core AVX f32 peak + DDR bandwidth + a loopback/
    # shared-memory "interconnect" proxy: generous enough that no real
    # CPU measurement crosses 1.0, stable enough that the proxy MFU
    # only moves when the code does.
    "cpu": (1e12, 2e11, 1e10),
}

# Per-chip HBM capacity in bytes (public TPU specs) — the denominator
# the hbm-overcommit analysis pass and the reshard-feasibility
# pre-flight compare static peak estimates against. ``cpu`` is None:
# host RAM is not a chip budget, so capacity checks are skipped there
# unless SPARKDL_TPU_HBM_BYTES pins one explicitly.
HBM_BYTES = {
    "v4": 32 * 2**30,
    "v5e": 16 * 2**30,
    "v5p": 95 * 2**30,
    "cpu": None,
}

def normalize_device_kind(kind):
    """Map a PJRT ``device_kind`` string (``"TPU v5 lite"``,
    ``"TPU v4"``, ``"cpu"``...) onto a :data:`PEAK_TABLE` key. A kind
    the table does not know is an error, never a default: a peak
    borrowed from another chip makes every utilization derived from
    it wrong without saying so."""
    k = (kind or "").lower()
    if "v5p" in k:
        return "v5p"
    if "v5e" in k or "v5 lite" in k or "v5lite" in k:
        return "v5e"
    if "v4" in k:
        return "v4"
    if "cpu" in k:
        return "cpu"
    raise ValueError(
        f"device kind {kind!r} is not in the peaks table "
        f"({sorted(PEAK_TABLE)}); add its published peaks to "
        "sparkdl_tpu.observe.perf.PEAK_TABLE")


def _kind_of(device):
    return getattr(device, "device_kind", "") or device.platform


def device_kind():
    """The probed device kind of this process's first jax device, or
    ``None`` when no jax backend is up in this process. Same rule as
    the heartbeat's memory gauges: a telemetry path must never be the
    thing that initializes a backend — in a gang worker that breaks
    the rendezvous, and in a driver on a TPU host it takes the chip
    from the workers about to be spawned."""
    from sparkdl_tpu.utils.jax_compat import initialized_jax

    jax = initialized_jax()
    if jax is None:
        return None
    try:
        return _kind_of(jax.devices()[0])
    except Exception:
        return None


def peak_flops(kind=None):
    """Peak FLOPs/s for ``kind`` (a raw ``device_kind`` string; default
    = the probed one). ``SPARKDL_TPU_PEAK_FLOPS`` overrides any kind —
    the pre-existing contract every bench honored."""
    env = os.environ.get(PEAK_FLOPS_ENV)
    if env:
        return float(env)
    return PEAK_TABLE[normalize_device_kind(kind or device_kind())][0]


def peak_bytes_per_sec(kind=None):
    """Peak HBM bytes/s for ``kind`` (env-overridable, like
    :func:`peak_flops`)."""
    env = os.environ.get(PEAK_BYTES_ENV)
    if env:
        return float(env)
    return PEAK_TABLE[normalize_device_kind(kind or device_kind())][1]


def peak_interconnect_bytes_per_sec(kind=None):
    """Aggregate per-chip ICI bytes/s for ``kind`` — the denominator
    the static comms budget (:mod:`sparkdl_tpu.analysis.comms`) turns
    wire bytes into predicted seconds with. Env-overridable via
    ``SPARKDL_TPU_PEAK_ICI_BYTES_PER_S``."""
    env = os.environ.get(PEAK_ICI_ENV)
    if env:
        return float(env)
    return PEAK_TABLE[normalize_device_kind(kind or device_kind())][2]


def hbm_capacity_bytes(kind=None):
    """Per-chip HBM capacity in bytes for ``kind``, or ``None`` when
    there is no chip budget to hold a program to: the kind has none
    (cpu), or no kind was given and this process has no device to ask
    (a driver that never imported jax). ``SPARKDL_TPU_HBM_BYTES``
    overrides any kind — the knob an operator with a nonstandard
    memory config (or a cpu rig that wants the overcommit pass live)
    pins."""
    env = os.environ.get(HBM_BYTES_ENV)
    if env:
        return float(env)
    kind = kind or device_kind()
    if kind is None:
        return None
    return HBM_BYTES[normalize_device_kind(kind)]


# -- step-time attribution ---------------------------------------------------


def _union(intervals):
    """Merge ``[(lo, hi), ...]`` into disjoint intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _measure(intervals):
    return sum(hi - lo for lo, hi in intervals)


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _intersect(a, b):
    """Intersection of two DISJOINT-SORTED interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b):
    """Interval list ``a`` minus union-list ``b`` (both disjoint
    sorted)."""
    out = []
    for lo, hi in a:
        cur = lo
        for blo, bhi in b:
            if bhi <= cur or blo >= hi:
                continue
            if blo > cur:
                out.append((cur, blo))
            cur = max(cur, bhi)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def step_breakdown(events, step_cat="train"):
    """Per-step wall-time attribution over raw timeline events (the
    merged-trace or drained-worker event dicts; ``ts``/``dur`` integer
    microseconds).

    Returns one dict per *execute-phase* step span (``cat ==
    step_cat``, ``ph == "X"``; the ``phase="compile"`` first-call span
    is excluded — compile wall time is not compute) in timestamp
    order::

        {"step": int|None, "ts": µs, "dur_s": float,
         "components": {compute, collective, host_callback,
                        data_wait, checkpoint},   # step-thread seconds
         "overlapped_collective_s": float,        # concurrent, extra
         "collective_total_s": float,
         "overlap_efficiency": float|None}

    Attribution rules:

    - A categorized span **on the step span's thread** is time the
      step thread was blocked in that wait; per-category time is the
      *union measure* of its intervals clipped to the step window, so
      nested spans (``allgather`` calling ``reduce``) never double
      count. ``compute`` is the uncovered remainder — components sum
      to the step duration by construction.
    - A **collective span on another thread** overlapping the step
      window is an async collective. The portion of it during which
      the step thread was computing (not inside any same-thread wait)
      is ``overlapped_collective_s`` — concurrent time, reported next
      to (not inside) the wall-time components.
    - ``overlap_efficiency`` = overlapped / (overlapped + serialized)
      collective time; ``None`` when the step ran no collectives.
    """
    steps = [e for e in events
             if e.get("ph") == "X" and e.get("cat") == step_cat
             and isinstance(e.get("ts"), (int, float))
             # the first call's span is XLA compile wall time
             # (instrument_step phase="compile"); attributing it
             # would report a 30s compile as "compute" and mask the
             # real split the compile-vs-execute histograms keep
             # separate
             and (e.get("args") or {}).get("phase") != "compile"]
    cats = {}
    for e in events:
        comp = _CAT_TO_COMPONENT.get(e.get("cat"))
        if comp is None or e.get("ph") != "X":
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        dur = e.get("dur", 0) or 0
        cats.setdefault(comp, []).append(
            (float(ts), float(ts) + float(dur), e.get("tid")))
    out = []
    for step in sorted(steps, key=lambda e: e["ts"]):
        lo = float(step["ts"])
        hi = lo + float(step.get("dur", 0) or 0)
        tid = step.get("tid")
        dur_s = (hi - lo) / 1e6
        components = {c: 0.0 for c in COMPONENTS}
        blocked = []
        async_collective = []
        for comp, spans in cats.items():
            same = _union(_clip(
                [(a, b) for a, b, t in spans if t == tid], lo, hi))
            components[comp] = _measure(same) / 1e6
            blocked.extend(same)
            if comp == "collective":
                async_collective = _union(_clip(
                    [(a, b) for a, b, t in spans if t != tid], lo, hi))
        blocked = _union(blocked)
        compute_iv = _subtract([(lo, hi)], blocked)
        components["compute"] = _measure(compute_iv) / 1e6
        overlapped = _measure(_intersect(async_collective, compute_iv)) / 1e6
        serialized = components["collective"]
        total_coll = serialized + _measure(async_collective) / 1e6
        eff = None
        if total_coll > 0:
            eff = overlapped / total_coll
        out.append({
            "step": step.get("args", {}).get("step"),
            "ts": step["ts"],
            "dur_s": dur_s,
            "components": components,
            "overlapped_collective_s": overlapped,
            "collective_total_s": total_coll,
            "overlap_efficiency": eff,
        })
    return out


def make_breakdown(total_s, components, *, source, extra=None):
    """The one breakdown document shape (``BREAKDOWN_SCHEMA``) of the
    telemetry-derived attribution, which ``perf.autotune``'s pruner
    reads. ``components`` maps name → seconds; fractions are derived
    here."""
    total_s = float(total_s)
    doc = {
        "schema": BREAKDOWN_SCHEMA,
        "source": source,
        "total_s": total_s,
        "components": {k: float(v) for k, v in components.items()},
        "fractions": {
            k: (float(v) / total_s if total_s > 0 else None)
            for k, v in components.items()
        },
    }
    if extra:
        doc.update(extra)
    return doc


def attribution_report(events, step_cat="train"):
    """Aggregate :func:`step_breakdown` over one process's events into
    the ``perf.json`` / doctor document: summed components (a
    :func:`make_breakdown` doc), overall overlap efficiency, the
    per-step rows, and ``inter_step_data_wait_s``. Zero instrumented
    steps → ``{"steps": 0}`` so callers can skip rendering.

    ``inter_step_data_wait_s`` is the data-wait time that fell
    BETWEEN step windows: in the canonical ``for batch in
    prefetch_to_device(...): stepped(batch)`` pattern the refill (and
    its ``data.wait`` span) runs when the for-loop advances the
    iterator, strictly between the step spans — so a starved input
    pipeline shows up here, not in the per-step ``data_wait``
    component (which only catches iterators consumed *inside* the
    step function). Outside-the-window time, reported next to — not
    inside — the sum-to-step-duration components, like the overlapped
    collective time."""
    rows = step_breakdown(events, step_cat=step_cat)
    if not rows:
        return {"steps": 0}
    totals = {c: 0.0 for c in COMPONENTS}
    for r in rows:
        for c, v in r["components"].items():
            totals[c] += v
    total_s = sum(r["dur_s"] for r in rows)
    overlapped = sum(r["overlapped_collective_s"] for r in rows)
    coll_total = sum(r["collective_total_s"] for r in rows)
    step_windows = _union([
        (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0) or 0))
        for e in events
        if e.get("ph") == "X" and e.get("cat") == step_cat
        and isinstance(e.get("ts"), (int, float))])
    data_spans = _union([
        (float(e["ts"]), float(e["ts"]) + float(e.get("dur", 0) or 0))
        for e in events
        if e.get("ph") == "X" and e.get("cat") == "data"
        and isinstance(e.get("ts"), (int, float))])
    inter_step_wait = _measure(_subtract(data_spans, step_windows)) / 1e6
    doc = make_breakdown(total_s, totals, source="timeline")
    doc.update({
        "steps": len(rows),
        "overlapped_collective_s": overlapped,
        "collective_total_s": coll_total,
        "overlap_efficiency": (overlapped / coll_total
                               if coll_total > 0 else None),
        "inter_step_data_wait_s": inter_step_wait,
        "per_step": rows,
    })
    return doc


# -- differential attribution (perf forensics) -------------------------------

REGRESSION_SCHEMA = "sparkdl_tpu.perf.regression/1"


def _report_from_rows(rows):
    """An :func:`attribution_report`-shaped doc aggregated from
    precomputed per-step rows (the capped-rows fallback: when only
    ``perf.json``'s ``per_step`` survive, the diff still runs — it
    just cannot name grown span names)."""
    totals = {c: 0.0 for c in COMPONENTS}
    for r in rows:
        for c, v in (r.get("components") or {}).items():
            if c in totals and isinstance(v, (int, float)):
                totals[c] += float(v)
    total_s = sum(float(r.get("dur_s") or 0.0) for r in rows)
    overlapped = sum(float(r.get("overlapped_collective_s") or 0.0)
                     for r in rows)
    coll = sum(float(r.get("collective_total_s") or 0.0) for r in rows)
    doc = make_breakdown(total_s, totals, source="rows")
    doc.update({
        "steps": len(rows),
        "overlapped_collective_s": overlapped,
        "collective_total_s": coll,
        "overlap_efficiency": (overlapped / coll if coll > 0 else None),
        "per_step": list(rows),
    })
    return doc


def _window_report(window, step_cat="train"):
    """Normalize one diff side into ``(attribution doc, raw events)``.

    Accepts — in order of forensic fidelity — a raw timeline event
    list (→ :func:`attribution_report`, span names available), a list
    of precomputed per-step rows (``components``/``dur_s`` dicts), or
    an already-built attribution/breakdown doc. ``(None, None)`` when
    the window carries nothing attributable."""
    if isinstance(window, dict):
        if "events" in window and isinstance(window["events"],
                                             (list, tuple)):
            events = list(window["events"])
            doc = attribution_report(events, step_cat=step_cat)
            if window.get("mfu") is not None and "mfu" not in doc:
                doc["mfu"] = window["mfu"]
            return (doc if doc.get("steps") else None,
                    events if doc.get("steps") else None)
        if "components" in window or "per_step" in window:
            return (window if window.get("steps") else None), None
        return None, None
    if isinstance(window, (list, tuple)):
        items = [w for w in window if isinstance(w, dict)]
        if not items:
            return None, None
        if all("components" in w and "dur_s" in w for w in items):
            return _report_from_rows(items), None
        doc = attribution_report(items, step_cat=step_cat)
        if not doc.get("steps"):
            return None, None
        return doc, items
    return None, None


def _per_step_components(doc):
    """Mean step-thread seconds per step for every component."""
    steps = doc.get("steps") or 0
    comps = doc.get("components") or {}
    if not steps:
        return {c: 0.0 for c in COMPONENTS}
    return {c: float(comps.get(c, 0.0) or 0.0) / steps
            for c in COMPONENTS}


def _span_seconds_per_step(events, steps, step_cat="train"):
    """Per-step seconds by span name over raw events (non-step X
    spans) — the grown-span-names half of the diff."""
    if not events or not steps:
        return {}
    by_name = {}
    for e in events:
        if e.get("ph") != "X" or e.get("cat") == step_cat:
            continue
        name = e.get("name")
        ts = e.get("ts")
        if not name or not isinstance(ts, (int, float)):
            continue
        dur = float(e.get("dur", 0) or 0) / 1e6
        by_name[name] = by_name.get(name, 0.0) + dur
    return {n: s / steps for n, s in by_name.items()}


def _window_summary(doc):
    steps = doc.get("steps") or 0
    total_s = float(doc.get("total_s") or 0.0)
    return {
        "steps": steps,
        "step_s_mean": (total_s / steps if steps else None),
        "components_per_step": _per_step_components(doc),
        "overlap_efficiency": doc.get("overlap_efficiency"),
        "mfu": doc.get("mfu"),
        "inter_step_data_wait_s": doc.get("inter_step_data_wait_s"),
    }


def diff_attribution(baseline_window, regressed_window, *,
                     step_cat="train", noise_floor_s=1e-3,
                     rel_floor=0.05, top_spans=5):
    """Differential step attribution: WHY did steps get slower between
    two windows (the alert rule's own calibration window vs the window
    that fired)?

    Each window may be a raw timeline event list, a list of per-step
    attribution rows, or an :func:`attribution_report` doc — the
    capped-rows fallback means a 200-row ``perf.json`` still diffs,
    it just cannot name grown spans. Returns a
    :data:`REGRESSION_SCHEMA` doc::

        {"schema", "baseline": {...}, "regressed": {...},
         "delta": {"step_s", "step_factor", "components_per_step",
                   "overlap_efficiency", "mfu"},
         "top_growing_component": name|None,   # None = under the floor
         "growth_fraction": {...},  # share of step growth, grown comps
         "top_growing_spans": [{"name", "baseline_s_per_step",
                                "regressed_s_per_step", "delta_s"}],
         "significant": bool, "noise_floor_s": float}

    or ``None`` when either side has no attributable steps. The noise
    floor — ``max(noise_floor_s, rel_floor × baseline step time)`` —
    keeps run-to-run jitter from being named a grown component: a
    zero-delta pair reports ``significant: False`` and no culprit.
    """
    base_doc, base_events = _window_report(baseline_window,
                                           step_cat=step_cat)
    reg_doc, reg_events = _window_report(regressed_window,
                                         step_cat=step_cat)
    if base_doc is None or reg_doc is None:
        return None
    base = _window_summary(base_doc)
    reg = _window_summary(reg_doc)
    step_delta = reg["step_s_mean"] - base["step_s_mean"]
    floor = max(float(noise_floor_s), rel_floor * base["step_s_mean"])
    comp_delta = {
        c: reg["components_per_step"][c] - base["components_per_step"][c]
        for c in COMPONENTS
    }
    grown = {c: d for c, d in comp_delta.items() if d > floor}
    significant = step_delta > floor and bool(grown)
    top_component = (max(grown, key=grown.get) if significant else None)
    growth_fraction = {}
    if significant and step_delta > 0:
        growth_fraction = {c: d / step_delta for c, d in grown.items()}
    eff_delta = None
    if isinstance(base.get("overlap_efficiency"), (int, float)) and \
            isinstance(reg.get("overlap_efficiency"), (int, float)):
        eff_delta = (reg["overlap_efficiency"]
                     - base["overlap_efficiency"])
    mfu_delta = None
    if isinstance(base.get("mfu"), (int, float)) and \
            isinstance(reg.get("mfu"), (int, float)):
        mfu_delta = reg["mfu"] - base["mfu"]
    spans = []
    if base_events is not None and reg_events is not None:
        base_spans = _span_seconds_per_step(
            base_events, base["steps"], step_cat=step_cat)
        reg_spans = _span_seconds_per_step(
            reg_events, reg["steps"], step_cat=step_cat)
        for name in set(base_spans) | set(reg_spans):
            d = reg_spans.get(name, 0.0) - base_spans.get(name, 0.0)
            if d > floor:
                spans.append({
                    "name": name,
                    "baseline_s_per_step": base_spans.get(name, 0.0),
                    "regressed_s_per_step": reg_spans.get(name, 0.0),
                    "delta_s": d,
                })
        spans.sort(key=lambda s: -s["delta_s"])
        spans = spans[:top_spans]
    return {
        "schema": REGRESSION_SCHEMA,
        "baseline": base,
        "regressed": reg,
        "delta": {
            "step_s": step_delta,
            "step_factor": (reg["step_s_mean"] / base["step_s_mean"]
                            if base["step_s_mean"] else None),
            "components_per_step": comp_delta,
            "overlap_efficiency": eff_delta,
            "mfu": mfu_delta,
        },
        "top_growing_component": top_component,
        "growth_fraction": growth_fraction,
        "top_growing_spans": spans,
        "significant": significant,
        "noise_floor_s": floor,
    }


def render_diff_lines(diff, indent=""):
    """Human-readable lines for one :func:`diff_attribution` doc — the
    SHARED renderer doctor, ``observe.compare --explain`` and the
    forensics report all use, so the three surfaces read alike."""
    if not diff:
        return []
    base, reg = diff["baseline"], diff["regressed"]
    d = diff["delta"]
    lines = [
        "%sstep time: %.4fs -> %.4fs (x%.2f, %+.4fs) over %d vs %d "
        "step(s)" % (
            indent, base["step_s_mean"], reg["step_s_mean"],
            d["step_factor"] or 0.0, d["step_s"],
            base["steps"], reg["steps"]),
    ]
    for c in COMPONENTS:
        delta = d["components_per_step"].get(c, 0.0)
        marker = ""
        if c == diff.get("top_growing_component"):
            marker = "  <-- grew the most"
        lines.append(
            "%s  %-13s %.4fs/step -> %.4fs/step (%+.4fs)%s" % (
                indent, c, base["components_per_step"].get(c, 0.0),
                reg["components_per_step"].get(c, 0.0), delta, marker))
    if d.get("overlap_efficiency") is not None:
        lines.append("%s  overlap efficiency %+.1f%%" % (
            indent, d["overlap_efficiency"] * 100))
    if d.get("mfu") is not None:
        lines.append("%s  mfu %+.4f" % (indent, d["mfu"]))
    for s in diff.get("top_growing_spans") or ():
        lines.append(
            "%s  span %-24s %+0.4fs/step (%.4fs -> %.4fs)" % (
                indent, s["name"], s["delta_s"],
                s["baseline_s_per_step"], s["regressed_s_per_step"]))
    if not diff.get("significant"):
        lines.append(
            "%s  (delta under the %.4fs noise floor — no component "
            "named)" % (indent, diff["noise_floor_s"]))
    return lines


# -- roofline / MFU gauges ---------------------------------------------------

# name -> {"flops": float|None, "bytes_accessed": float|None}; written
# only behind the latch, so with telemetry off this dict never grows
# (the zero-overhead test pins that).
_step_costs = {}


def register_step_cost(name, executable):
    """Record one executable's analytic cost (FLOPs / bytes accessed /
    peak memory) so every subsequent :func:`note_step` can turn step
    wall time into achieved-FLOPs/s and MFU. ``executable`` is a
    ``Lowered`` or ``Compiled`` (the shims duck-type); a runtime with
    no cost model degrades to ``None`` and the gauges simply never
    appear. No-op (returns None) with telemetry off."""
    from sparkdl_tpu import observe
    from sparkdl_tpu.utils import jax_compat

    if not observe.enabled():
        return None
    cost = jax_compat.cost_analysis(executable)
    mem = jax_compat.memory_analysis(executable)
    if mem:
        # static budget for the OOM report's measured-vs-predicted line
        from sparkdl_tpu.observe import mem as mem_acct

        mem_acct.note_budget(name, mem)
    entry = {
        "flops": (cost or {}).get("flops"),
        "bytes_accessed": (cost or {}).get("bytes_accessed"),
    }
    if not any(v for v in entry.values()):
        return None
    # Resolve the device kind and peak denominators ONCE — they are
    # process-lifetime constants, and note_step runs on every
    # executed step of the instrumented hot path. An executable in
    # hand means a backend is up, so asking it directly starts none.
    import jax

    kind = _kind_of(jax.devices()[0])
    entry["device_kind"] = normalize_device_kind(kind)
    entry["peak_flops"] = peak_flops(kind)
    entry["peak_bytes"] = peak_bytes_per_sec(kind)
    _step_costs[name] = entry
    if entry["flops"]:
        observe.set_gauge("step_cost_flops", entry["flops"], fn=name)
    if entry["bytes_accessed"]:
        observe.set_gauge("step_cost_bytes", entry["bytes_accessed"],
                          fn=name)
        if entry["flops"]:
            observe.set_gauge(
                "step_operational_intensity",
                entry["flops"] / entry["bytes_accessed"], fn=name)
    if mem and mem.get("temp_size_in_bytes") is not None:
        observe.set_gauge("step_temp_bytes", mem["temp_size_in_bytes"],
                          fn=name)
    return entry


def note_step(name, seconds):
    """Fold one executed step's wall time into the achieved-rate and
    roofline gauges — called by ``instrument_step`` on every
    execute-phase step (already behind the latch). Silent when no cost
    was registered for ``name`` (the missing-cost-model contract)."""
    from sparkdl_tpu import observe

    entry = _step_costs.get(name)
    if not entry or seconds <= 0:
        return
    norm = entry["device_kind"]
    flops, nbytes = entry.get("flops"), entry.get("bytes_accessed")
    if flops:
        achieved = flops / seconds
        observe.set_gauge("achieved_flops_per_sec", achieved, fn=name)
        pf = entry["peak_flops"]
        if pf:
            observe.set_gauge("mfu", achieved / pf, fn=name,
                              device_kind=norm)
    if nbytes:
        achieved_b = nbytes / seconds
        observe.set_gauge("achieved_bytes_per_sec", achieved_b, fn=name)
        pb = entry["peak_bytes"]
        if pb:
            observe.set_gauge("membw_util", achieved_b / pb, fn=name,
                              device_kind=norm)


def _reset_for_tests():
    _step_costs.clear()


# -- regression ledger -------------------------------------------------------


def default_history_path():
    """``benchmarks/results/history.jsonl`` at the repo root (env
    ``SPARKDL_TPU_PERF_HISTORY`` overrides; the values ``0`` / ``off``
    disable appending entirely)."""
    env = os.environ.get(HISTORY_ENV)
    if env and env.lower() not in ("0", "off"):
        return env
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "benchmarks", "results", "history.jsonl")


def host_fingerprint():
    """Stable who-measured-this string: comparisons across different
    fingerprints are apples-to-oranges and the compare CLI says so."""
    import platform as _platform

    return "%s/%s/cpu%s" % (
        socket.gethostname(), _platform.machine(), os.cpu_count() or 0)


def git_sha():
    """Short HEAD sha of the repo this module sits in, or None."""
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    try:
        out = subprocess.run(
            ["git", "-C", root, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        sha = out.stdout.strip()
        return sha or None
    except Exception:
        return None


def _percentile(samples, q):
    """np.percentile's default linear interpolation, without the
    numpy import this artifact-side module avoids."""
    xs = sorted(float(s) for s in samples)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    f, c = int(k), min(int(k) + 1, len(xs) - 1)
    return xs[f] + (xs[c] - xs[f]) * (k - f)


def sample_metric(samples, *, unit, higher_is_better=False, digits=4):
    """ONE ledger metric dict from raw per-rep samples (already in the
    target unit): ``value`` = p50, with ``p99`` and the samples
    preserved so :mod:`sparkdl_tpu.observe.compare`'s median/IQR noise
    protection applies. The single definition of the shape
    :func:`history_record` documents — benchmarks must not hand-roll
    copies of it."""
    if not samples:
        raise ValueError("sample_metric needs at least one sample")
    p50 = round(_percentile(samples, 50), digits)
    return {
        "value": p50, "p50": p50,
        "p99": round(_percentile(samples, 99), digits),
        "samples": [round(float(s), digits) for s in samples],
        "unit": unit, "higher_is_better": higher_is_better,
    }


def history_record(metrics, *, device_kind=None, bench=None, extra=None):
    """One schema-versioned ledger line. ``metrics`` maps name →
    ``{"value": float, "unit": str, "samples": [...]?, "p50"?,
    "p99"?, "higher_is_better"?: bool}`` (plain numbers are wrapped).
    """
    norm = {}
    for name, m in metrics.items():
        if not isinstance(m, dict):
            m = {"value": m}
        if m.get("value") is None:
            continue
        norm[name] = {k: v for k, v in m.items() if v is not None}
    rec = {
        "schema": HISTORY_SCHEMA,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": git_sha(),
        "host": host_fingerprint(),
        "device_kind": device_kind,
        "bench": bench,
        "metrics": norm,
    }
    if extra:
        rec.update(extra)
    return rec


def append_history(record, path=None):
    """Append one record as a JSON line (creating parents). Best
    effort and silent on failure — the ledger must never fail the
    bench that feeds it. Returns the path written, or None when
    disabled/unwritable."""
    env = os.environ.get(HISTORY_ENV, "")
    if env.lower() in ("0", "off"):
        return None
    path = path or default_history_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")
        return path
    except Exception:
        return None


def read_history(path=None):
    """Parsed ledger entries (skipping unparsable lines), oldest
    first. Missing file → empty list."""
    path = path or default_history_path()
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        return []
    return out
