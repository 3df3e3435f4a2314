"""Warm-start compilation core (sparkdl_tpu/parallel/compile.py) on
CPU inside the tier-1 box: serialize→deserialize→execute parity,
fingerprint sensitivity, and the corrupt-entry degradation contract.
"""

import logging
import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.parallel.compile import (
    JAX_CACHE_DIR_ENV,
    CompiledStepCache,
    enable_persistent_cache,
    load_or_compile,
    step_fingerprint,
)


@pytest.fixture()
def cache(tmp_path):
    return CompiledStepCache(str(tmp_path / "aot"))


def _lowered_train_step():
    """A real (tiny) train step through the stock factory — the
    artifact shape the gang path caches."""
    from sparkdl_tpu.parallel.train import make_train_step

    def loss_fn(p, b):
        return ((b @ p["w"]) ** 2).mean()

    opt = optax.adamw(1e-3)
    params = {"w": jnp.arange(12.0, dtype=jnp.float32).reshape(4, 3) / 10}
    opt_state = opt.init(params)
    batch = jnp.ones((2, 4), jnp.float32)
    step = make_train_step(loss_fn, opt)
    lowered = jax.jit(step).lower(params, opt_state, batch)
    return lowered, (params, opt_state, batch)


def test_deserialized_step_is_bit_identical_to_cold_compile(cache):
    """The acceptance bar: the executable served from the cache
    produces byte-for-byte the arrays the cold-compiled one does."""
    lowered, args = _lowered_train_step()
    cold = cache.load_or_compile(lowered)
    assert (cache.hits, cache.misses) == (0, 1)

    warm = cache.load_or_compile(lowered)
    assert (cache.hits, cache.misses) == (1, 1)

    p_cold, s_cold, m_cold = cold(*args)
    p_warm, s_warm, m_warm = warm(*args)
    for a, b in zip(jax.tree.leaves((p_cold, s_cold, m_cold)),
                    jax.tree.leaves((p_warm, s_warm, m_warm))):
        na, nb = np.asarray(a), np.asarray(b)
        assert na.dtype == nb.dtype
        assert na.tobytes() == nb.tobytes()


def test_cache_entry_survives_process_boundary_shape(cache):
    """A second CompiledStepCache over the same dir (what a relaunched
    worker builds) hits the first one's entry."""
    lowered, args = _lowered_train_step()
    cache.load_or_compile(lowered)

    relaunched = CompiledStepCache(cache.cache_dir)
    warm = relaunched.load_or_compile(lowered)
    assert (relaunched.hits, relaunched.misses) == (1, 0)
    assert np.isfinite(float(np.asarray(warm(*args)[2]["loss"])))


def test_fingerprint_changes_on_topology_and_options():
    """Any change in (topology, compile options, program) must miss —
    a serialized executable is only valid for the world that built
    it. Same inputs must hit (content-addressing, not object id)."""
    lowered, _ = _lowered_train_step()
    text = lowered.as_text()
    base = step_fingerprint(text, topology="cpu|x86|d1|p1")
    assert base == step_fingerprint(text, topology="cpu|x86|d1|p1")
    assert base != step_fingerprint(text, topology="tpu|v5e|d8|p2")
    assert base != step_fingerprint(text, topology="cpu|x86|d2|p1")
    assert base != step_fingerprint(
        text, topology="cpu|x86|d1|p1",
        compiler_options={"xla_cpu_enable_fast_math": True})
    assert base != step_fingerprint(
        text + "\n", topology="cpu|x86|d1|p1")


def test_option_change_misses_in_cache(cache):
    lowered, _ = _lowered_train_step()
    cache.load_or_compile(lowered)
    cache.load_or_compile(
        lowered, compiler_options={"xla_embed_ir_in_executable": True})
    assert (cache.hits, cache.misses) == (0, 2)


def test_truncated_entry_degrades_to_cold_compile(cache, caplog):
    """The corrupt-cache contract: WARNING + cold compile + rewrite,
    never an exception (a preempted rank's half-written entry must not
    kill its replacement)."""
    lowered, args = _lowered_train_step()
    cache.load_or_compile(lowered)
    path = cache._entry_path(cache.fingerprint(lowered))
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 3])

    with caplog.at_level(logging.WARNING, logger="HorovodRunner"):
        compiled = cache.load_or_compile(lowered)
    assert cache.misses == 2
    assert any("falling back to cold compile" in r.message
               for r in caplog.records)
    assert np.isfinite(float(np.asarray(compiled(*args)[2]["loss"])))
    # the entry was rewritten whole: the next load hits again
    assert cache.load_or_compile(lowered) is not None
    assert cache.hits == 1


def test_garbage_and_mismatched_entries_degrade(cache, caplog):
    lowered, _ = _lowered_train_step()
    fp = cache.fingerprint(lowered)
    path = cache._entry_path(fp)
    # valid pickle, wrong shape entirely
    with open(path, "wb") as f:
        pickle.dump(["not", "an", "entry"], f)
    with caplog.at_level(logging.WARNING, logger="HorovodRunner"):
        cache.load_or_compile(lowered)
    assert cache.misses == 1
    # right shape, wrong fingerprint (e.g. a hash-collision-adjacent
    # manual copy between topologies)
    entry = pickle.load(open(path, "rb"))
    entry["fingerprint"] = "0" * 64
    with open(path, "wb") as f:
        pickle.dump(entry, f)
    with caplog.at_level(logging.WARNING, logger="HorovodRunner"):
        cache.load_or_compile(lowered)
    assert cache.misses == 2


@pytest.mark.parametrize("read_at_import", [False, True])
def test_enable_persistent_cache_sets_no_other_directory(
        tmp_path, monkeypatch, read_at_import):
    """``JAX_COMPILATION_CACHE_DIR`` is the one place the cache lives.
    Where JAX read it at import, no directory is set in code at all;
    where the variable came later, JAX is told of that same one."""
    import sparkdl_tpu.parallel.compile as compile_mod

    # enable_persistent_cache mutates process-global jax config;
    # restore it or every later test in this pytest process silently
    # compiles against this test's (soon-deleted) tmp dir.
    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_raise_persistent_cache_errors",
        )
    }
    saved_latch = compile_mod._persistent_cache_dir
    d = str(tmp_path / "xla-cache")
    monkeypatch.setenv(JAX_CACHE_DIR_ENV, d)
    try:
        if read_at_import:
            jax.config.update("jax_compilation_cache_dir", d)
        updated = []
        update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, value: (updated.append(name),
                                 update(name, value))[1])
        assert enable_persistent_cache() == d and os.path.isdir(d)
        assert ("jax_compilation_cache_dir" in updated) \
            is (not read_at_import)
        assert jax.config.jax_compilation_cache_dir == d
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        monkeypatch.undo()
        for name, value in saved.items():
            jax.config.update(name, value)
        compile_mod._persistent_cache_dir = saved_latch


def test_enable_persistent_cache_noop_without_optin(monkeypatch):
    monkeypatch.delenv(JAX_CACHE_DIR_ENV, raising=False)
    assert enable_persistent_cache() is None


def test_module_level_load_or_compile_without_optin(monkeypatch):
    """Library code calls load_or_compile unconditionally; with no
    cache dir configured it must be a plain cold compile."""
    monkeypatch.delenv(JAX_CACHE_DIR_ENV, raising=False)
    lowered, args = _lowered_train_step()
    compiled = load_or_compile(lowered)
    assert np.isfinite(float(np.asarray(compiled(*args)[2]["loss"])))


def test_observe_counters_and_instants(tmp_path, monkeypatch):
    """The warm-start story's acceptance signal: hit/miss counters and
    timeline instants land in the observe layer when telemetry is on."""
    from sparkdl_tpu import observe

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV,
                       str(tmp_path / "telemetry"))
    observe._reset_for_tests()
    try:
        lowered, _ = _lowered_train_step()
        c = CompiledStepCache(str(tmp_path / "aot"))
        c.load_or_compile(lowered)
        c.load_or_compile(lowered)
        snap = observe.metrics().snapshot()
        counters = {c["name"]: c["value"] for c in snap["counters"]}
        assert counters["compile_cache_misses_total"] == 1
        assert counters["compile_cache_hits_total"] == 1
        hist = [h for h in snap["histograms"]
                if h["name"] == "compile_seconds"]
        assert {h["labels"].get("source") for h in hist} == \
            {"cache", "xla"}
        names = [e["name"] for e in observe.timeline().drain()]
        assert "compile_cache.miss" in names
        assert "compile_cache.hit" in names
    finally:
        observe._reset_for_tests()


def test_aot_entries_pruned_beyond_cap(cache, monkeypatch):
    """Superseded fingerprints can never hit again; writes prune the
    oldest entries beyond SPARKDL_TPU_COMPILE_CACHE_MAX_AOT."""
    import time

    monkeypatch.setenv("SPARKDL_TPU_COMPILE_CACHE_MAX_AOT", "3")
    for i in range(5):
        p = cache._entry_path(f"{i:064d}")
        with open(p, "wb") as f:
            f.write(b"x")
        past = time.time() - (100 - i)
        os.utime(p, (past, past))
    lowered, _ = _lowered_train_step()
    cache.load_or_compile(lowered)   # write #6 triggers the prune
    names = sorted(n for n in os.listdir(cache.cache_dir)
                   if n.startswith("aot-"))
    assert len(names) == 3, names
    # the oldest synthetic entries went first; the real one survives
    assert cache._entry_path(cache.fingerprint(lowered)).endswith(
        tuple(names))


def test_compile_cache_memory_category(monkeypatch, tmp_path):
    """ISSUE 18: every executable the cache serves folds its
    generated-code size into the ``compile_cache`` accounting
    category. Tolerant of runtimes whose memory analysis omits
    ``generated_code_size_in_bytes`` — the category then legitimately
    reads 0."""
    from sparkdl_tpu import observe
    from sparkdl_tpu.observe import mem

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path / "tel"))
    observe._reset_for_tests()
    try:
        c = CompiledStepCache(str(tmp_path / "aot"))
        lowered, _ = _lowered_train_step()
        c.load_or_compile(lowered)
        cats = mem.sample_now()["categories"]
        assert "compile_cache" in cats
        size = (c.last_memory_stats or {}).get(
            "generated_code_size_in_bytes")
        if size:
            assert cats["compile_cache"] == int(size)
        else:
            assert cats["compile_cache"] == 0
    finally:
        observe._reset_for_tests()
