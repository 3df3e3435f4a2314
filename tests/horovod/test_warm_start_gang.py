"""The warm-start acceptance proof (ISSUE: perf_opt): a chaos-killed
gang's supervised relaunch serves its step executable from the
compile cache — cold-compile on attempt 1, cache-hit on attempt 2,
and time-to-first-resumed-step strictly below the cold path — all
visible in the merged telemetry artifacts.

Marked like the other gang chaos proofs: ``chaos`` + ``slow`` so the
time-boxed tier-1 gate stays honest; CI runs them in the chaos step.
"""

import glob
import json
import os

import pytest

from sparkdl import HorovodRunner
from sparkdl_tpu import observe

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def fresh_observe():
    observe._reset_for_tests()
    yield
    observe._reset_for_tests()


def _warm_start_main(ckpt_dir, total_steps):
    """A checkpointed train loop whose jitted step is heavy enough
    that XLA compile time dwarfs deserialize time, served through
    CompiledStepCache. The worker bootstrap already pointed the
    persistent cache at JAX_COMPILATION_CACHE_DIR; this main uses
    the AOT layer on top, exactly as a production main would."""
    import time

    t_main0 = time.perf_counter()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.horovod import restart_context
    from sparkdl_tpu.parallel.compile import CompiledStepCache
    from sparkdl_tpu.utils.chaos import chaos_step
    from sparkdl_tpu.utils.checkpoint import TrainCheckpointer

    hvd.init()
    ctx = restart_context()

    # Unrolled matmul chain: ~64 fused tanh(x@w) layers cost XLA real
    # compile work (seconds on CPU) while deserializing the finished
    # executable costs ~10ms — the gap the test measures.
    def step(w, x):
        for _ in range(64):
            x = jnp.tanh(x @ w) + 0.01 * x
        return w - 1e-3 * jnp.tanh(x), x.mean()

    w = jnp.full((96, 96), 0.01, jnp.float32)
    x = jnp.ones((96, 96), jnp.float32)

    # Checkpointer set up BEFORE the timed compile-or-deserialize
    # window on EVERY attempt (latest_step materializes the orbax
    # manager), so the cold/warm first-step comparison isolates the
    # compile path instead of charging attempt 2 for orbax imports
    # attempt 1 would only pay after its first step.
    ckpt = TrainCheckpointer(ckpt_dir)
    start = 0
    if ctx.resume_step is not None:
        restored = ckpt.restore(
            ctx.resume_step,
            target={"w": np.zeros((96, 96), np.float32)})
        w = jnp.asarray(restored["w"])
        start = ctx.resume_step + 1
    else:
        ckpt.latest_step()

    lowered = jax.jit(step, donate_argnums=(0,)).lower(w, x)
    compiled = CompiledStepCache().load_or_compile(lowered)

    first_step_logged = False
    try:
        for s in range(start, total_steps):
            w, loss = compiled(w, x)
            if not first_step_logged:
                # Time-to-first-(resumed-)step: main entry → first
                # step result on device, compile path included.
                float(np.asarray(loss))
                observe.instant(
                    "train.first_step", cat="train",
                    attempt=ctx.attempt, rank=hvd.rank(),
                    seconds=round(time.perf_counter() - t_main0, 4))
                first_step_logged = True
            # numpy, not jax.Array: each rank's array is process-local
            # in the multi-process gang world, which orbax refuses to
            # serialize (replicated host state is the gang contract).
            ckpt.save(s, {"w": np.asarray(w)})
            ckpt.wait_until_finished()
            hvd.barrier()
            chaos_step(s)
    finally:
        ckpt.close()
    return {"attempt": ctx.attempt,
            "w_sum": float(np.asarray(w).sum())}


@pytest.mark.gang
@pytest.mark.slow
def test_relaunched_gang_warm_starts_from_compile_cache(monkeypatch,
                                                        tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV,
                       str(tmp_path / "telemetry"))
    observe._reset_for_tests()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "compile-cache"))
    monkeypatch.setenv("SPARKDL_TPU_GANG_MAX_RETRIES", "2")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_BASE", "0.1")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_MAX", "0.2")
    monkeypatch.setenv("SPARKDL_TPU_GANG_RESUME_DIR",
                       str(tmp_path / "ck"))
    monkeypatch.setenv("SPARKDL_TPU_ABORT_GRACE", "5")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_KILL_RANK", "1")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_KILL_STEP", "1")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_ONCE_FILE",
                       str(tmp_path / "one-kill"))

    result = HorovodRunner(np=-2).run(
        _warm_start_main, ckpt_dir=str(tmp_path / "ck"), total_steps=5)
    assert result["attempt"] == 1          # the relaunch happened

    (run,) = glob.glob(str(tmp_path / "telemetry" / "run-*"))

    # -- metrics: the relaunch HIT the cache ------------------------
    prom = open(os.path.join(run, "metrics.prom")).read()
    hits = [l for l in prom.splitlines()
            if l.startswith("compile_cache_hits_total")]
    assert hits and sum(
        float(l.rsplit(" ", 1)[1]) for l in hits) >= 1, prom
    misses = [l for l in prom.splitlines()
              if l.startswith("compile_cache_misses_total")]
    assert misses and sum(
        float(l.rsplit(" ", 1)[1]) for l in misses) >= 1, prom

    # -- timeline: cold-compile, kill, then cache-hit, in order -----
    trace = json.loads(open(os.path.join(run, "timeline.json")).read())
    events = [e for e in trace["traceEvents"] if e["ph"] != "M"]

    def ts_of(name, **match):
        cands = [e["ts"] for e in events
                 if e["name"] == name
                 and all(e["args"].get(k) == v for k, v in match.items())]
        assert cands, (
            f"event {name} {match} missing; have "
            f"{sorted({e['name'] for e in events})}")
        return min(cands)

    miss_ts = ts_of("compile_cache.miss")
    kill_ts = ts_of("chaos.kill", rank=1, step=1)
    hit_ts = ts_of("compile_cache.hit")
    assert miss_ts < kill_ts < hit_ts

    # -- the headline: resumed first-step beats the cold path -------
    first_steps = {}
    for e in events:
        if e["name"] == "train.first_step":
            first_steps.setdefault(
                e["args"]["attempt"], []).append(e["args"]["seconds"])
    assert 0 in first_steps and 1 in first_steps, first_steps
    cold = min(first_steps[0])
    warm = max(first_steps[1])
    assert warm < cold, (
        f"warm start not faster: attempt-2 first step {warm}s vs "
        f"attempt-1 cold {cold}s")
