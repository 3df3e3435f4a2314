"""Gang-wide memory accounting, leak detection support, and OOM
forensics (ISSUE 18).

The platform's remaining blind axis is memory: the heartbeat carries
one HBM gauge and one alert says "HBM high", but nothing says *what*
is using it, *which* category is growing, or *why* a run died at
RESOURCE_EXHAUSTED. This module composes the existing subsystems into
a memory-observability layer:

- **Categorized accounting** — long-lived trees are registered once by
  category (``params``, ``opt_state``, ``kv_pages``, ``compile_cache``,
  ``host_prefetch``); a low-rate sampler thread
  (``sparkdl-tpu-mem-sampler``) snapshots
  :func:`~sparkdl_tpu.utils.jax_compat.device_memory_stats` /
  :func:`~sparkdl_tpu.utils.jax_compat.live_buffer_bytes` plus host RSS
  into ``mem_bytes{category=}`` / ``host_rss_bytes`` gauges, aggregates
  the largest live buffers by (shape, dtype), and computes an
  ``unattributed`` residual (live − Σ categories) that surfaces leaks
  outside any registered tree.
- **Beacon + flight recorder** — every sample is folded into a compact
  dict (:func:`beacon_sample`) that rides the heartbeat into the
  driver's ``live_state`` (statusz panel, leak alert rules) and is
  emitted as a ``mem.sample`` timeline instant, which the worker's
  flight-recorder mirror persists so an OOM-killed rank's memory tail
  survives SIGKILL.
- **OOM forensics** — :func:`oom_guard` wraps step execution and engine
  admission; an allocation failure writes ``oom_report.json`` (sample
  tail, category table at death, largest buffers, measured peak vs the
  static ``memory_analysis`` budget, actionable hints) before the
  exception propagates.

Behind the PR 3 telemetry latch end to end: without
``SPARKDL_TPU_TELEMETRY_DIR`` there is no sampler thread, no per-step
work, and no report writing — :func:`maybe_start_sampler` is a single
boolean test. Host RSS is read from ``/proc/self/status`` (fallback
``resource.getrusage``) so the accounting works on CPU-only CI; device
stats go through the ``jax_compat`` shims, which never import jax.

Env knobs (registered in ``utils/knobs.py``):

- ``SPARKDL_TPU_MEM_SAMPLE_S`` — sampler period in seconds (default 2)
- ``SPARKDL_TPU_MEM_TOP_BUFFERS`` — rows kept in the largest-live-
  buffer table (default 8)
- ``SPARKDL_TPU_MEM_SAMPLES`` — rolling sample-tail length kept for the
  beacon and the OOM report (default 64)
"""

import collections
import contextlib
import json
import os
import sys
import threading
import time

SAMPLE_S_ENV = "SPARKDL_TPU_MEM_SAMPLE_S"
DEFAULT_SAMPLE_S = 2.0
TOP_BUFFERS_ENV = "SPARKDL_TPU_MEM_TOP_BUFFERS"
DEFAULT_TOP_BUFFERS = 8
SAMPLES_ENV = "SPARKDL_TPU_MEM_SAMPLES"
DEFAULT_SAMPLES = 64

#: The category vocabulary. register_tree accepts anything, but the
#: platform's own call sites stick to these so the doctor and the docs
#: can name them.
CATEGORIES = ("params", "opt_state", "kv_pages", "compile_cache",
              "host_prefetch")

OOM_REPORT_SCHEMA = "sparkdl_tpu.observe.mem/oom_report/1"

#: Substrings that identify an allocation failure across backends: XLA
#: raises RuntimeError/XlaRuntimeError with RESOURCE_EXHAUSTED, the
#: paged KV pool raises its own dead-end RuntimeError, and pure-host
#: paths raise MemoryError.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "resource_exhausted",
                "Out of memory", "out of memory", "OOM",
                "paged pool exhausted")

_lock = threading.Lock()
_trees = {}            # category -> int | callable() -> int
_samples = None        # deque of sample dicts (created on first use)
_latest = None         # last sample dict
_budgets = {}          # fn name -> memory_analysis dict (static budget)
_host_rss_high = 0     # high-water of sampled VmRSS
_sampler = None
_sampler_stop = None


def _env_float(name, default):
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def _env_int(name, default):
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


# -- host RSS ----------------------------------------------------------------


def host_rss_bytes():
    """Current resident set size of this process in bytes, or None
    when unreadable. ``/proc/self/status`` first (current RSS, Linux);
    ``getrusage`` high-water as the portable fallback."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        # ru_maxrss is KB on Linux: high-water, not current — still the
        # right order of magnitude for accounting without /proc.
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:
        return None


def host_rss_high_water_bytes():
    """High-water host RSS in bytes: the max of every sampled VmRSS and
    the kernel's own ``ru_maxrss`` accounting (which needs no sampler
    thread — benches call this once at the end of a run)."""
    high = _host_rss_high
    try:
        import resource

        high = max(high,
                   resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                   * 1024)
    except Exception:
        pass
    return high or None


def device_peak_bytes():
    """Peak device-memory use in bytes from the runtime's allocator
    stats, falling back to currently-live buffer bytes; None when the
    backend exposes neither (CPU)."""
    from sparkdl_tpu.utils import jax_compat

    stats = jax_compat.device_memory_stats()
    if stats and stats.get("peak_bytes_in_use") is not None:
        return int(stats["peak_bytes_in_use"])
    return jax_compat.live_buffer_bytes()


# -- categorized accounting --------------------------------------------------


def tree_nbytes(tree):
    """Σ leaf nbytes over a pytree without importing jax: uses
    ``jax.tree_util`` only when jax is already in the process, else
    duck-types nbytes on the object itself."""
    jax = sys.modules.get("jax")
    leaves = None
    if jax is not None:
        try:
            leaves = jax.tree_util.tree_leaves(tree)
        except Exception:
            leaves = None
    if leaves is None:
        leaves = [tree]
    total = 0
    for leaf in leaves:
        n = getattr(leaf, "nbytes", None)
        if isinstance(n, (int, float)):
            total += int(n)
    return total


def register_tree(category, tree):
    """Register a long-lived tree (params, opt state, ...) under a
    category. ``tree`` may be a pytree of arrays (sized once, now), an
    int byte count, or a zero-arg callable re-evaluated at every sample
    (for pools whose size moves, e.g. ``kv_pages``). Re-registering a
    category replaces it. Returns the current byte count (0 for
    callables until sampled). No-op (returns None) with telemetry
    off."""
    from sparkdl_tpu import observe

    if not observe.enabled():
        return None
    if callable(tree):
        sized = tree
        now = 0
    elif isinstance(tree, (int, float)):
        sized = int(tree)
        now = sized
    else:
        sized = tree_nbytes(tree)
        now = sized
    with _lock:
        _trees[str(category)] = sized
    return now


def set_category_bytes(category, nbytes):
    """Point update for a category whose size the owner tracks itself
    (the serving KV pool). No-op with telemetry off."""
    register_tree(category, int(nbytes))


def clear_category(category):
    with _lock:
        _trees.pop(str(category), None)


def category_bytes():
    """The category table right now: {category: bytes}. Callables are
    evaluated; a failing callable reports 0 rather than raising."""
    with _lock:
        items = list(_trees.items())
    table = {}
    for cat, sized in items:
        if callable(sized):
            try:
                table[cat] = int(sized() or 0)
            except Exception:
                table[cat] = 0
        else:
            table[cat] = int(sized)
    return table


def note_budget(name, analysis):
    """Record a compiled executable's static ``memory_analysis`` dict
    as the budget the OOM report sets measured peak against. Called by
    ``perf.register_step_cost`` (already behind the latch)."""
    if not analysis:
        return
    with _lock:
        _budgets[str(name)] = dict(analysis)


def static_budget_bytes():
    """Σ static peak over registered executables: arguments + outputs +
    temps (aliased pairs counted once is the shim's business); None
    when nothing was registered."""
    with _lock:
        budgets = list(_budgets.values())
    if not budgets:
        return None
    total = 0
    for b in budgets:
        for key in ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes"):
            v = b.get(key)
            if v:
                total += int(v)
        alias = b.get("alias_size_in_bytes")
        if alias:
            total -= int(alias)
    return max(0, total)


def largest_buffers(top_n=None):
    """The largest live device buffers aggregated by (shape, dtype):
    ``[{"shape", "dtype", "count", "bytes"}, ...]`` sorted by bytes
    descending. Empty when no jax backend is up in this process —
    never raises, never initializes one."""
    if top_n is None:
        top_n = _env_int(TOP_BUFFERS_ENV, DEFAULT_TOP_BUFFERS)
    from sparkdl_tpu.utils.jax_compat import initialized_jax

    jax = initialized_jax()
    if jax is None:
        return []
    agg = {}
    try:
        for arr in jax.live_arrays():
            n = getattr(arr, "nbytes", None)
            if not isinstance(n, (int, float)):
                continue
            key = (str(getattr(arr, "shape", "?")),
                   str(getattr(arr, "dtype", "?")))
            cnt, tot = agg.get(key, (0, 0))
            agg[key] = (cnt + 1, tot + int(n))
    except Exception:
        return []
    rows = [{"shape": shape, "dtype": dtype, "count": cnt, "bytes": tot}
            for (shape, dtype), (cnt, tot) in agg.items()]
    rows.sort(key=lambda r: r["bytes"], reverse=True)
    return rows[:top_n]


# -- sampling ----------------------------------------------------------------


def _samples_deque():
    global _samples
    if _samples is None:
        _samples = collections.deque(
            maxlen=max(4, _env_int(SAMPLES_ENV, DEFAULT_SAMPLES)))
    return _samples


def sample_now():
    """Take one sample: set the gauges, append to the rolling tail,
    emit the ``mem.sample`` instant (which the flight-recorder mirror
    persists), and return the sample dict. No-op (returns None) with
    telemetry off. This is what the sampler thread calls each tick;
    benches may call it synchronously."""
    global _latest, _host_rss_high
    from sparkdl_tpu import observe
    from sparkdl_tpu.utils import jax_compat

    if not observe.enabled():
        return None
    rss = host_rss_bytes()
    stats = jax_compat.device_memory_stats() or {}
    live = jax_compat.live_buffer_bytes()
    cats = category_bytes()
    attributed = sum(cats.values())
    unattributed = None
    if live is not None:
        unattributed = max(0, int(live) - attributed)
    sample = {
        "ts": time.time(),
        "rss": rss,
        "hbm": (int(stats["bytes_in_use"])
                if stats.get("bytes_in_use") is not None else live),
        "peak": (int(stats["peak_bytes_in_use"])
                 if stats.get("peak_bytes_in_use") is not None else None),
        "limit": (int(stats["bytes_limit"])
                  if stats.get("bytes_limit") is not None else None),
        "live": live,
        "categories": cats,
        "unattributed": unattributed,
    }
    with _lock:
        if rss:
            _host_rss_high = max(_host_rss_high, rss)
        _latest = sample
    _samples_deque().append(sample)
    if rss is not None:
        observe.set_gauge("host_rss_bytes", rss)
    for cat, nbytes in cats.items():
        observe.set_gauge("mem_bytes", nbytes, category=cat)
    if unattributed is not None:
        observe.set_gauge("mem_bytes", unattributed,
                          category="unattributed")
    observe.instant(
        "mem.sample", cat="mem", rss=rss, hbm=sample["hbm"],
        unattributed=unattributed)
    return sample


def beacon_sample():
    """The compact dict that rides the heartbeat: the latest sample
    minus the timestamp bulk. ``{}`` when no sample was taken yet (or
    telemetry is off) — the heartbeat payload stays small and the
    driver treats a missing field as 'no data'."""
    with _lock:
        sample = _latest
    if not sample:
        return {}
    out = {"rss": sample["rss"], "hbm": sample["hbm"],
           "unattributed": sample["unattributed"],
           "categories": sample["categories"]}
    return {k: v for k, v in out.items() if v is not None}


def sample_tail(n=16):
    return list(_samples_deque())[-n:]


def maybe_start_sampler(interval=None):
    """Start the low-rate sampler thread — behind the latch: without
    ``SPARKDL_TPU_TELEMETRY_DIR`` this returns None and NO thread
    exists (the zero-overhead contract, pinned by the thread-name-scan
    test). Idempotent. An interval <= 0 disables the thread (benches
    can still call :func:`sample_now` synchronously)."""
    global _sampler, _sampler_stop
    from sparkdl_tpu import observe

    if not observe.enabled():
        return None
    if _sampler is not None and _sampler.is_alive():
        return _sampler
    if interval is None:
        interval = _env_float(SAMPLE_S_ENV, DEFAULT_SAMPLE_S)
    if interval <= 0:
        return None
    _sampler_stop = stop = threading.Event()

    def loop():
        while not stop.wait(interval):
            try:
                sample_now()
            except Exception:
                # accounting must never take down the worker
                pass

    _sampler = threading.Thread(
        target=loop, name="sparkdl-tpu-mem-sampler", daemon=True)
    _sampler.start()
    # One synchronous sample so the first heartbeat after start already
    # carries a mem field instead of waiting a full period.
    try:
        sample_now()
    except Exception:
        pass
    return _sampler


def stop_sampler():
    global _sampler, _sampler_stop
    if _sampler_stop is not None:
        _sampler_stop.set()
    if _sampler is not None:
        _sampler.join(timeout=5.0)
    _sampler = None
    _sampler_stop = None


# -- OOM forensics -----------------------------------------------------------


def is_oom(exc):
    """True when ``exc`` looks like an allocation failure: MemoryError,
    or any exception whose text carries a known OOM marker
    (RESOURCE_EXHAUSTED from XLA, the paged-pool dead-end, ...)."""
    if isinstance(exc, MemoryError):
        return True
    text = f"{type(exc).__name__}: {exc}"
    return any(marker in text for marker in _OOM_MARKERS)


def _hints(phase, sample, budget):
    hints = []
    if phase == "admission":
        hints.append(
            "KV pool exhausted: raise PagedKVConfig.n_pages (or lower "
            "max_new_tokens / concurrent sequences); weight-only quant "
            "(SPARKDL_TPU_SERVE_QUANT=int8) frees HBM for more pages.")
    else:
        hints.append(
            "Undonated step buffers double params+opt_state at the "
            "peak: run `python -m sparkdl_tpu.analysis` donation "
            "checks and apply the fixer's donate_argnums patch.")
        hints.append(
            "Restore-time high-water: SPARKDL_TPU_RESHARD_GROUPED=1 "
            "bounds resharding to one parameter group at a time.")
    if budget is not None and sample and sample.get("peak") is not None \
            and sample["peak"] > budget:
        hints.append(
            f"Measured peak {sample['peak']} B exceeds the static "
            f"memory_analysis budget {budget} B — runtime allocations "
            "(collectives scratch, prefetch) are on top of the compiled "
            "program; leave headroom or shrink the step.")
    unattributed = (sample or {}).get("unattributed")
    attributed = sum(((sample or {}).get("categories") or {}).values())
    if unattributed and unattributed > max(attributed, 1):
        hints.append(
            "Most live bytes are unattributed (outside every registered "
            "tree) — a leak candidate; diff consecutive mem.sample "
            "instants / the largest-buffer table to find the grower.")
    return hints


def _report_dir(run_dir=None):
    from sparkdl_tpu import observe

    if run_dir:
        return run_dir
    return (os.environ.get("SPARKDL_TPU_JOB_DIR")
            or observe.telemetry_dir())


def oom_report_path(out_dir, rank=None):
    """``oom_report.json`` in ``out_dir``, rank-suffixed when two ranks
    share the dir and the plain name is taken."""
    base = os.path.join(out_dir, "oom_report.json")
    if rank is None or not os.path.exists(base):
        return base
    return os.path.join(out_dir, f"oom_report-rank-{rank}.json")


def write_oom_report(phase, error, run_dir=None, extra=None):
    """Write ``oom_report.json``: the forensic record of an allocation
    failure. Returns the path, or None when telemetry is off or no
    writable dir exists. Never raises — this runs inside an exception
    handler that must re-raise the real error."""
    from sparkdl_tpu import observe

    if not observe.enabled():
        return None
    out_dir = _report_dir(run_dir)
    if not out_dir:
        return None
    try:
        # a final sample so the table reflects the moment of death
        sample = sample_now() or (_latest or {})
    except Exception:
        sample = _latest or {}
    rank = os.environ.get("SPARKDL_TPU_RANK")
    budget = static_budget_bytes()
    report = {
        "schema": OOM_REPORT_SCHEMA,
        "ts": time.time(),
        "phase": phase,
        "rank": int(rank) if rank is not None else None,
        "error": str(error)[:4000],
        "host_rss_bytes": (sample or {}).get("rss"),
        "host_rss_high_water_bytes": host_rss_high_water_bytes(),
        "device": {k: (sample or {}).get(k)
                   for k in ("hbm", "peak", "limit", "live")},
        "categories": (sample or {}).get("categories") or category_bytes(),
        "unattributed": (sample or {}).get("unattributed"),
        "largest_buffers": largest_buffers(),
        "static_budget_bytes": budget,
        "sample_tail": sample_tail(),
        "hints": _hints(phase, sample, budget),
    }
    if extra:
        report["extra"] = extra
    try:
        os.makedirs(out_dir, exist_ok=True)
        path = oom_report_path(out_dir, rank=rank)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        return None
    try:
        observe.instant("mem.oom", cat="mem", phase=phase,
                        error=str(error)[:200])
        observe.inc("oom_reports_total", phase=phase)
        observe.flush()    # the process is probably about to die
    except Exception:
        pass
    return path


@contextlib.contextmanager
def oom_guard(phase="step", run_dir=None, extra=None):
    """Wrap an allocation-prone block (step execution, engine
    admission): an exception that looks like an allocation failure
    writes ``oom_report.json`` before propagating; every other
    exception passes through untouched. Zero work on the happy path
    and with telemetry off."""
    try:
        yield
    except BaseException as e:
        from sparkdl_tpu import observe

        if observe.enabled() and is_oom(e):
            write_oom_report(phase, e, run_dir=run_dir, extra=extra)
        raise


def _reset_for_tests():
    global _trees, _samples, _latest, _budgets, _host_rss_high
    stop_sampler()
    with _lock:
        _trees = {}
        _budgets = {}
        _latest = None
        _host_rss_high = 0
    _samples = None


__all__ = [
    "CATEGORIES", "OOM_REPORT_SCHEMA", "tree_nbytes",
    "register_tree", "set_category_bytes", "clear_category",
    "category_bytes", "largest_buffers",
    "note_budget", "static_budget_bytes",
    "host_rss_bytes", "host_rss_high_water_bytes", "device_peak_bytes",
    "sample_now", "beacon_sample", "sample_tail",
    "maybe_start_sampler", "stop_sampler",
    "is_oom", "oom_guard", "write_oom_report", "oom_report_path",
]
