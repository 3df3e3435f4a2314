"""Warm-start compilation: persistent XLA compile cache + serialized
AOT step executables across gang relaunches.

PR 1 made supervised relaunch the *normal* recovery path for a
preempted gang — but every relaunched attempt still re-paid the full
trace + XLA compile of the train step (minutes at Llama scale) before
the first resumed step executed. Production trainers (MaxText et al.)
solve exactly this with ahead-of-time compilation plus JAX's
persistent compilation cache; this module is that story for
HorovodRunner gangs, in two layers:

1. :func:`enable_persistent_cache` — turn on JAX's *persistent
   compilation cache* (``jax_compilation_cache_dir``) with sane
   min-compile-time/min-entry-size knobs. Every ``jit`` in the process
   then reuses on-disk XLA artifacts across process restarts — no code
   changes in user mains.
2. :class:`CompiledStepCache` — serialize the *whole compiled step
   executable* (``jax.experimental.serialize_executable``) keyed by a
   fingerprint of (jax version, backend/platform, topology, compile
   options, StableHLO module hash). ``load_or_compile(lowered)`` turns
   restart-to-first-step from a compile-bound stall into a
   deserialize-and-go, and reuses the single lowering
   :func:`sparkdl_tpu.parallel.train.lower_train_step` /
   ``analysis.register_preflight`` already produce — nothing is traced
   twice::

       lowered = lower_train_step(step, params, opt_state, batch,
                                  mesh=mesh)
       analysis.register_preflight(lowered)        # graph lint
       compiled = CompiledStepCache().load_or_compile(lowered)

Where the cache lives: ``JAX_COMPILATION_CACHE_DIR``, JAX's own
variable, and nothing else (:func:`persistent_cache_dir` reads it).
JAX itself reads it, every process that inherits the environment uses
that directory, and nothing here points the cache anywhere else;
unset, warm-start compilation is off. A program that wants a cache
without being told where (``chip_smoke.py``) calls
:func:`export_cache_dir`, which exports the fixed in-checkout
``.jax_cache`` under that same name — the path is part of JAX's cache
key, so it is never built from a temp name, a uid, a pid or a time.

Gang wiring: the launcher passes the driver's environment to every
worker (local, remote and supervised relaunches alike) and
``_worker.py`` calls :func:`enable_persistent_cache` *before* backend
init, so a preempted rank's replacement warm-starts from its
predecessor's cache entries.

Degradation contract: a corrupt, truncated, or fingerprint-mismatched
AOT entry falls back to a cold ``lowered.compile()`` with a WARNING —
never an exception — and the entry is rewritten. Cache files are
host-local pickles; treat the cache dir with the same trust as the
code dir (the operator owns both).

Observability (:mod:`sparkdl_tpu.observe`, off by default):
``compile_cache_hits_total`` / ``compile_cache_misses_total``
counters, a ``compile_seconds{source="cache"|"xla"}`` histogram, and
``compile_cache.hit`` / ``compile_cache.miss`` timeline instants — so
a chaos run's merged trace visibly shows cold-compile on attempt 1
and cache-hit on attempt 2.
"""

import hashlib
import logging
import os
import pickle
import sys
import tempfile
import time

logger = logging.getLogger("HorovodRunner")

JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
MIN_COMPILE_S_ENV = "SPARKDL_TPU_COMPILE_CACHE_MIN_COMPILE_S"
MIN_ENTRY_BYTES_ENV = "SPARKDL_TPU_COMPILE_CACHE_MIN_BYTES"
MAX_AOT_ENTRIES_ENV = "SPARKDL_TPU_COMPILE_CACHE_MAX_AOT"

# AOT entries have no natural eviction (every jax upgrade or graph
# change strands the old fingerprint's file forever), so writes prune
# beyond a cap, oldest-mtime first. The default leaves room for a
# full pod host's worth of per-rank entries across a few program
# versions; real Llama-scale executables are large, so the cap is
# deliberately modest.
DEFAULT_MAX_AOT_ENTRIES = 64

# Persist anything that took >= 1s to compile regardless of size, and
# anything at all above 0 bytes after that gate: the cache exists for
# the minutes-long train-step compile, but a relaunch also re-pays
# many sub-second helper jits whose artifacts are cheap to keep.
DEFAULT_MIN_COMPILE_S = 1.0
DEFAULT_MIN_ENTRY_BYTES = 0

# Format 2 added `memory_stats` to the entry (recorded at write time —
# a deserialized executable's memory_analysis drops alias accounting,
# and a caller that reports the step's peak needs the real figures on
# warm starts too); format 3 `device_ids`, the devices the executable
# loads onto. Older entries simply cold-recompile once.
_AOT_FORMAT = 3

_persistent_cache_dir = None  # latched by enable_persistent_cache


def persistent_cache_dir(environ=None):
    """The cache root, ``JAX_COMPILATION_CACHE_DIR``, or None when
    warm-start compilation is not opted in."""
    env = os.environ if environ is None else environ
    return env.get(JAX_CACHE_DIR_ENV) or None


def export_cache_dir(environ=None):
    """Make sure ``JAX_COMPILATION_CACHE_DIR`` is set for this process
    and the children that inherit ``environ``: left alone when set,
    else the fixed, git-ignored ``<checkout>/.jax_cache``. Call before
    ``jax`` is imported — JAX reads the variable once, at import.
    Returns the directory."""
    env = os.environ if environ is None else environ
    if not env.get(JAX_CACHE_DIR_ENV):
        import sparkdl_tpu

        env[JAX_CACHE_DIR_ENV] = os.path.join(
            os.path.dirname(os.path.dirname(
                os.path.abspath(sparkdl_tpu.__file__))),
            ".jax_cache")
    return env[JAX_CACHE_DIR_ENV]


def enable_persistent_cache():
    """Turn on JAX's persistent compilation cache in the directory
    :func:`persistent_cache_dir` names, with this package's thresholds.
    Returns that directory, or None when none is configured (no-op —
    the opt-out path costs one env read). No other directory is ever
    set: JAX read the variable itself at import, and is told of it
    here only where the variable was set after that.

    Must run before the first compilation to be effective; the gang
    worker bootstrap calls it before backend init. Idempotent.
    """
    cache_dir = persistent_cache_dir()
    if not cache_dir:
        return None
    global _persistent_cache_dir
    # The whole degrade contract applies HERE too: this runs at worker
    # bootstrap before the control plane exists, so an unwritable dir
    # (a mount one host lacks) or a malformed threshold env must say
    # so and continue cold — raising would kill every rank of every
    # supervised attempt with a boot death the driver can't explain.
    # A cold compile hides no device, but it must not be silent: the
    # line also goes to stderr, which a worker's boot log keeps even
    # before its log tee exists.
    try:
        import jax

        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_enable_compilation_cache", True)
        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ.get(MIN_COMPILE_S_ENV,
                                 DEFAULT_MIN_COMPILE_S)))
        jax.config.update(
            "jax_persistent_cache_min_entry_size_bytes",
            int(os.environ.get(MIN_ENTRY_BYTES_ENV,
                               DEFAULT_MIN_ENTRY_BYTES)))
        # Cache problems (corrupt entry) degrade to a cold compile
        # with JAX's own warning, never crash the step; pin the
        # default in case a site config flipped it.
        jax.config.update("jax_raise_persistent_cache_errors", False)
    except Exception as e:
        msg = (f"persistent compile cache unavailable under {cache_dir} "
               f"({type(e).__name__}: {e}); continuing with cold "
               "compiles")
        logger.warning(msg)
        print(f"sparkdl_tpu: {msg}", file=sys.stderr, flush=True)
        return None
    if _persistent_cache_dir != cache_dir:
        _persistent_cache_dir = cache_dir
        logger.info("persistent XLA compile cache enabled: %s", cache_dir)
    return cache_dir


def topology_descriptor():
    """A stable string naming the world this process compiles for:
    platform, device kind, device/process counts, this process's index
    and its local device ids. Any change (a v5e cache served to a v4
    gang, a resized gang) must miss — a serialized executable is only
    valid on the topology it was built for. The per-process fields
    matter inside a gang: each rank's single-device step executable
    embeds ITS device assignment, so rank 1 must never deserialize
    rank 0's entry (the runtime would reject it — "does not have any
    local devices"). Same-rank relaunches land on the same index/ids
    and hit."""
    import jax

    devs = jax.devices()
    return "|".join((
        devs[0].platform,
        getattr(devs[0], "device_kind", "") or "",
        f"d{len(devs)}",
        f"p{jax.process_count()}",
        f"i{jax.process_index()}",
        "l" + ",".join(str(d.id) for d in jax.local_devices()),
    ))


def step_fingerprint(stablehlo_text, *, topology=None,
                     compiler_options=None):
    """Content-address one lowered program for the AOT executable
    cache: sha256 over (jax version, topology descriptor, compile
    options, StableHLO module text). The StableHLO hash — not the
    Python function — is the identity, so an edited-but-equivalent
    main still hits and any real graph change misses."""
    import jax

    if topology is None:
        topology = topology_descriptor()
    h = hashlib.sha256()
    h.update(f"aot{_AOT_FORMAT}".encode())
    h.update(jax.__version__.encode())
    h.update(b"\0" + topology.encode())
    opts = sorted((compiler_options or {}).items())
    h.update(b"\0" + repr(opts).encode())
    h.update(b"\0" + stablehlo_text.encode())
    return h.hexdigest()


class CompiledStepCache:
    """Disk cache of AOT-compiled step executables.

    One entry per :func:`step_fingerprint`, written atomically
    (tmp + rename) so a preemption mid-write leaves no torn entry for
    the replacement rank to trip on. ``hits`` / ``misses`` count this
    instance's outcomes (a caller tells a warm start by them);
    the gang-wide view rides the observe counters.
    """

    def __init__(self, cache_dir=None):
        cache_dir = cache_dir or persistent_cache_dir()
        if not cache_dir:
            raise ValueError(
                "CompiledStepCache needs a cache directory: pass one or "
                f"set {JAX_CACHE_DIR_ENV}"
            )
        self.cache_dir = os.path.abspath(cache_dir)
        os.makedirs(self.cache_dir, exist_ok=True)
        self.hits = 0
        self.misses = 0
        # Compiled memory analysis of the newest load_or_compile
        # result. Persisted inside the cache entry at write time
        # because a DESERIALIZED executable's runtime drops the alias
        # accounting (alias_size reads 0) — without the stored stats a
        # warm-started caller would overstate its step's peak.
        self.last_memory_stats = None
        # Device-side program footprint of every executable this
        # instance served (generated_code_size_in_bytes per
        # fingerprint), exposed as the "compile_cache" accounting
        # category — no-op without the telemetry latch.
        self._code_bytes = {}
        from sparkdl_tpu.observe import mem as mem_acct

        mem_acct.register_tree(
            "compile_cache", lambda: sum(self._code_bytes.values()))

    def _entry_path(self, fingerprint):
        return os.path.join(self.cache_dir, f"aot-{fingerprint}.bin")

    def fingerprint(self, lowered, compiler_options=None, topology=None):
        from sparkdl_tpu.utils import jax_compat

        return step_fingerprint(
            jax_compat.lowered_stablehlo(lowered),
            topology=topology,
            compiler_options=compiler_options,
        )

    def _try_load(self, path, fingerprint):
        """The deserialization path, wrapped so EVERY failure mode —
        missing file, truncated pickle, foreign format, fingerprint
        drift, a deserialize the runtime rejects — degrades to a cold
        compile. Returns a Compiled or None."""
        import jax
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )

        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if (entry.get("format") != _AOT_FORMAT
                    or entry.get("fingerprint") != fingerprint):
                raise ValueError(
                    f"entry format/fingerprint mismatch "
                    f"(format={entry.get('format')!r})"
                )
            # Load onto the devices the program was compiled for: the
            # loader's default is every device of the backend, and an
            # executable built for one device of eight then expects
            # eight shards of every argument.
            by_id = {d.id: d for d in jax.devices()}
            compiled = deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=[
                    by_id[i] for i in entry["device_ids"]],
            )
            # Stats recorded at write time (guaranteed present since
            # format 2): the deserialized runtime's own
            # memory_analysis loses alias accounting.
            self.last_memory_stats = entry.get("memory_stats")
            return compiled
        except FileNotFoundError:
            return None
        except Exception as e:
            logger.warning(
                "compile cache entry %s unusable (%s: %s); falling back "
                "to cold compile and rewriting it",
                os.path.basename(path), type(e).__name__, e,
            )
            return None

    def _write(self, path, fingerprint, compiled):
        from jax.experimental.serialize_executable import serialize

        try:
            payload, in_tree, out_tree = serialize(compiled)
            blob = pickle.dumps({
                "format": _AOT_FORMAT,
                "fingerprint": fingerprint,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
                "device_ids": [
                    d.id for d in
                    compiled.runtime_executable().local_devices()],
                # Kept alongside the executable: deserialization loses
                # the alias accounting, so a warm start reads the peak
                # from here instead of a zeroed memory_analysis().
                # load_or_compile records (and alias-corrects) the
                # stats just before every _write.
                "memory_stats": self.last_memory_stats,
            })
            fd, tmp = tempfile.mkstemp(
                dir=self.cache_dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:
            # An unwritable/full cache dir must never fail the step
            # that just compiled fine.
            logger.warning(
                "could not persist AOT step executable to %s (%s: %s)",
                path, type(e).__name__, e,
            )
            return
        self._prune()

    def _prune(self):
        """Drop the oldest AOT entries beyond the cap — superseded
        fingerprints (jax upgrades, graph edits) can never hit again
        and would otherwise accumulate forever. Best-effort: a
        concurrent rank unlinking the same file is fine."""
        try:
            cap = int(os.environ.get(
                MAX_AOT_ENTRIES_ENV, DEFAULT_MAX_AOT_ENTRIES))
            entries = []
            for name in os.listdir(self.cache_dir):
                if not (name.startswith("aot-") and name.endswith(".bin")):
                    continue
                p = os.path.join(self.cache_dir, name)
                try:
                    entries.append((os.stat(p).st_mtime, p))
                except OSError:
                    continue
            for _, p in sorted(entries)[:max(0, len(entries) - cap)]:
                try:
                    os.unlink(p)
                except OSError:
                    pass
        except Exception:
            pass

    def load_or_compile(self, lowered, *, name="train_step",
                        compiler_options=None):
        """Return a ready ``jax.stages.Compiled`` for ``lowered``:
        deserialized from the cache on a fingerprint hit, else cold-
        compiled (and the entry written for the next incarnation).
        ``compiler_options`` are part of the fingerprint AND forwarded
        to the cold compile, so an options change can never serve a
        stale executable."""
        from sparkdl_tpu import observe

        fp = self.fingerprint(lowered, compiler_options=compiler_options)
        path = self._entry_path(fp)
        t0 = time.perf_counter()
        compiled = self._try_load(path, fp)
        if compiled is not None:
            dt = time.perf_counter() - t0
            self.hits += 1
            observe.inc("compile_cache_hits_total")
            observe.observe_value("compile_seconds", dt, source="cache")
            observe.instant("compile_cache.hit", cat="compile",
                            fn=name, fingerprint=fp[:12],
                            seconds=round(dt, 4))
            logger.info(
                "warm start: %s served from AOT cache in %.3fs "
                "(fingerprint %s)", name, dt, fp[:12],
            )
            self._register_cost(name, compiled, lowered)
            self._note_code_size(fp)
            return compiled
        self.misses += 1
        with observe.span("compile", cat="compile", fn=name,
                          fingerprint=fp[:12]):
            if compiler_options:
                compiled = lowered.compile(
                    compiler_options=dict(compiler_options))
            else:
                compiled = lowered.compile()
        from sparkdl_tpu.utils import jax_compat

        stats = jax_compat.memory_analysis(compiled)
        if stats is not None and not stats.get("alias_size_in_bytes"):
            # `.compile()` may have been served by the XLA persistent
            # cache (still an AOT miss here), and a deserialized
            # executable reports alias 0 even for donated programs.
            # Restore the donated bytes from the lowering's own
            # donation attrs so the stats this entry persists — and
            # every warm start after it — stay truthful.
            from sparkdl_tpu.analysis.fixes import donated_bytes_static

            static = donated_bytes_static(
                jax_compat.lowered_stablehlo(lowered))
            if static:
                stats = dict(stats, alias_size_in_bytes=static)
        self.last_memory_stats = stats
        dt = time.perf_counter() - t0
        observe.inc("compile_cache_misses_total")
        observe.observe_value("compile_seconds", dt, source="xla")
        observe.instant("compile_cache.miss", cat="compile",
                        fn=name, fingerprint=fp[:12],
                        seconds=round(dt, 4))
        self._write(path, fp, compiled)
        self._register_cost(name, compiled, lowered)
        self._note_code_size(fp)
        return compiled

    def _note_code_size(self, fingerprint):
        """Fold this executable's program size into the
        "compile_cache" accounting category (its generated code lives
        in device memory for as long as the executable does)."""
        size = (self.last_memory_stats or {}).get(
            "generated_code_size_in_bytes")
        if size:
            self._code_bytes[fingerprint] = int(size)

    @staticmethod
    def _register_cost(name, compiled, lowered):
        """Feed the executable's analytic FLOPs/bytes into
        :mod:`sparkdl_tpu.observe.perf` so every instrumented step of
        this program reports achieved-FLOPs/s and MFU. Behind the
        telemetry latch inside ``register_step_cost``; a deserialized
        executable whose runtime refuses the cost model falls back to
        the lowering's estimate, and no cost model at all just means
        the gauges never appear."""
        from sparkdl_tpu import observe
        from sparkdl_tpu.observe import perf

        if not observe.enabled():
            return
        if perf.register_step_cost(name, compiled) is None:
            perf.register_step_cost(name, lowered)


def load_or_compile(lowered, *, name="train_step", compiler_options=None):
    """Module-level convenience: :meth:`CompiledStepCache.
    load_or_compile` against the env-configured cache dir, or a plain
    cold compile when warm-start compilation is not opted in — so
    library code can call this unconditionally."""
    if persistent_cache_dir() is None:
        if compiler_options:
            return lowered.compile(compiler_options=dict(compiler_options))
        return lowered.compile()
    return CompiledStepCache().load_or_compile(
        lowered, name=name, compiler_options=compiler_options
    )
