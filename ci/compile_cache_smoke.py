#!/usr/bin/env python
"""CI compile-cache smoke (ISSUE: warm-start compilation satellite):
launch the same tiny instrumented gang TWICE against one fresh
``JAX_COMPILATION_CACHE_DIR`` and FAIL the build unless the second
launch's merged ``metrics.prom`` shows ``compile_cache_hits_total >=
1`` — the end-to-end proof that the launcher ships the cache dir, the
worker bootstrap enables it before backend init, and
``CompiledStepCache`` serves the relaunch from disk.

Usage::

    JAX_COMPILATION_CACHE_DIR=<dir> \\
    SPARKDL_TPU_TELEMETRY_DIR=<dir> python ci/compile_cache_smoke.py

(defaults: ``./compile-cache`` and ``./compile-cache-telemetry``).
Runs OUTSIDE the time-boxed tier-1 pytest gate — its own workflow
step; the workflow uploads the cache dir listing with the telemetry
artifacts.
"""

import glob
import os
import sys

# Runnable as `python ci/compile_cache_smoke.py` from a checkout.
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def _aot_gang_main(steps):
    """A jitted step served through CompiledStepCache: launch 1
    cold-compiles and writes the entry, launch 2 deserializes it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.parallel.compile import CompiledStepCache

    hvd.init()

    def step(w, x):
        for _ in range(8):
            x = jnp.tanh(x @ w) + 0.01 * x
        return w - 1e-3 * jnp.tanh(x), x.mean()

    w = jnp.full((32, 32), 0.01, jnp.float32)
    x = jnp.ones((32, 32), jnp.float32)
    lowered = jax.jit(step, donate_argnums=(0,)).lower(w, x)
    cache = CompiledStepCache()
    compiled = cache.load_or_compile(lowered)
    for _ in range(steps):
        w, loss = compiled(w, x)
    return {"rank": hvd.rank(), "size": hvd.size(),
            "warm": cache.hits > 0,
            "loss": float(np.asarray(loss))}


def fail(msg):
    print(f"COMPILE-CACHE SMOKE FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def _hits_total(prom_path):
    try:
        with open(prom_path) as f:
            prom = f.read()
    except OSError as e:
        fail(f"metrics.prom missing: {e}")
    return sum(
        float(line.rsplit(" ", 1)[1])
        for line in prom.splitlines()
        if line.startswith("compile_cache_hits_total")
    )


def main():
    cache_dir = os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.getcwd(), "compile-cache"),
    )
    out_dir = os.environ.setdefault(
        "SPARKDL_TPU_TELEMETRY_DIR",
        os.path.join(os.getcwd(), "compile-cache-telemetry"),
    )
    os.environ.setdefault("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    if glob.glob(os.path.join(cache_dir, "aot-*")):
        fail(f"cache dir {cache_dir} is not fresh; the cold/warm "
             "distinction would be meaningless")

    from sparkdl import HorovodRunner

    first = HorovodRunner(np=-2).run(_aot_gang_main, steps=2)
    print("launch 1 (cold):", first)
    second = HorovodRunner(np=-2).run(_aot_gang_main, steps=2)
    print("launch 2 (warm):", second)

    if first["warm"]:
        fail("launch 1 reported a cache hit against a fresh dir")
    if not second["warm"]:
        fail("launch 2 did not warm-start from the compile cache")
    if second["loss"] != first["loss"]:
        fail(f"deserialized executable diverged: "
             f"{second['loss']} != {first['loss']}")

    runs = sorted(glob.glob(os.path.join(out_dir, "run-*")))
    if len(runs) != 2:
        fail(f"expected two run dirs under {out_dir}, found {runs}")
    cold_hits = _hits_total(os.path.join(runs[0], "metrics.prom"))
    warm_hits = _hits_total(os.path.join(runs[1], "metrics.prom"))
    if cold_hits != 0:
        fail(f"launch 1 metrics.prom shows {cold_hits} cache hits")
    if warm_hits < 1:
        fail(f"launch 2 metrics.prom shows compile_cache_hits_total="
             f"{warm_hits}; expected >= 1")

    entries = sorted(
        os.path.basename(p)
        for p in glob.glob(os.path.join(cache_dir, "*")))
    print(f"cache dir {cache_dir}:")
    for e in entries:
        print(f"  {e}")
    if not any(e.startswith("aot-") for e in entries):
        fail("no AOT entries in the cache dir")
    print(f"compile-cache smoke OK: hits={warm_hits} on launch 2; "
          f"artifacts under {out_dir}")


if __name__ == "__main__":
    main()
