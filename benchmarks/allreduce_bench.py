#!/usr/bin/env python
"""hvd collective bandwidth benchmark (the BASELINE.json secondary
metric: "hvd.allreduce vs lax.psum bandwidth").

Two sections:

- gang (default): a HorovodRunner gang (np from argv, default -2)
  measures the shim's end-to-end collective bandwidth — tensor in,
  reduced tensor out, including the host<->device crossings — for
  allreduce, reducescatter (must move ~1/n the bytes of allreduce),
  and broadcast, against the raw in-jit ``lax.psum`` the shim lowers
  to. On a pod the gap is the shim's host-bridge overhead; JAX-native
  mains avoid it entirely by staying under jit.
- ``--tpu``: IN-PROCESS on the accelerator (this host has ONE chip, so
  size=1 makes the collective semantics identity — what this measures
  honestly is the real per-call cost of each path ON TPU: the
  numpy-in/numpy-out shim, the device-resident ``reduce_jax`` fast
  path, and the raw H2D/D2H bridge each collective call otherwise
  pays). Multi-chip ICI numbers still require a pod.

Usage: python benchmarks/allreduce_bench.py [np]      (gang section)
       python benchmarks/allreduce_bench.py --tpu     (on-chip section)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timeit(fn, reps=10):
    """Mean seconds per call plus the raw per-rep samples (the ledger
    wants p50/p99, not a single mean a noisy rep can poison)."""
    fn()  # warm (compile/caches)
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return sum(samples) / len(samples), samples


def _ms_metric(samples):
    """Seconds samples -> one ms ledger metric (the shared
    ``perf.sample_metric`` shape — compare's median/IQR protection
    needs the samples, not bare percentiles)."""
    from sparkdl_tpu.observe import perf

    return perf.sample_metric([s * 1e3 for s in samples], unit="ms",
                              digits=3)


def _pcts(samples):
    m = _ms_metric(samples)
    return m["p50"], m["p99"]


def bench_main(sizes_mb):
    import time

    import numpy as np

    import sparkdl_tpu.hvd as hvd

    hvd.init()

    # In-jit oracle: the same program the shim compiles for its default
    # op (Average: psum + in-graph divide), but timed on a
    # DEVICE-RESIDENT sharded array — no numpy crossings. shim_time -
    # injit_time is the host-bridge overhead JAX-native mains never pay
    # (they stay under jit end to end).
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, d)
    mesh = Mesh(np.array([by_proc[p] for p in sorted(by_proc)]), ("hvd",))
    psum = jax.jit(
        jax.shard_map(
            lambda x: jax.lax.psum(x, "hvd") / jax.lax.axis_size("hvd"),
            mesh=mesh, in_specs=P("hvd"), out_specs=P(),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )

    def busbw(mb, dt):
        # algorithmic bus bandwidth: 2*(n-1)/n * bytes / time
        return round(2 * (hvd.size() - 1) / hvd.size() * mb / 1024 / dt, 3)

    results = []
    metrics = {}
    reps = 5
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        # dim0 divisible by size for reducescatter
        n -= n % hvd.size()
        x = np.ones((n,), np.float32)
        dt, s_ar = _timeit(lambda: hvd.allreduce(x), reps)
        # reducescatter returns only this rank's 1/n chunk — one
        # psum_scatter, ~1/n the interconnect bytes of allreduce
        dt_rs, s_rs = _timeit(
            lambda: hvd.reducescatter(x, op=hvd.Sum), reps)
        dt_bc, s_bc = _timeit(lambda: hvd.broadcast(x, root_rank=0), reps)
        # the async path's steady-state cost: submit + result with no
        # compute between — the overlap win on a real step is this
        # wall time minus whatever compute it hides under
        dt_async, s_async = _timeit(
            lambda: hvd.allreduce_async(x, op=hvd.Sum).result(), reps)

        local = jax.device_put(x[None], by_proc[jax.process_index()])
        xg = jax.make_array_from_single_device_arrays(
            (hvd.size(),) + x.shape, NamedSharding(mesh, P("hvd")), [local]
        )
        dt_jit, s_jit = _timeit(lambda: psum(xg).block_until_ready(), reps)

        ar50, ar99 = _pcts(s_ar)
        rs50, rs99 = _pcts(s_rs)
        results.append({
            "size_mb": mb,
            "shim_time_ms": round(dt * 1e3, 3),
            "shim_time_ms_p50": ar50, "shim_time_ms_p99": ar99,
            "shim_busbw_gbps": busbw(mb, dt),
            "reducescatter_time_ms": round(dt_rs * 1e3, 3),
            "reducescatter_time_ms_p50": rs50,
            "reducescatter_time_ms_p99": rs99,
            "reducescatter_vs_allreduce": round(dt_rs / dt, 3),
            "broadcast_time_ms": round(dt_bc * 1e3, 3),
            "allreduce_async_roundtrip_ms": round(dt_async * 1e3, 3),
            "injit_time_ms": round(dt_jit * 1e3, 3),
            "injit_busbw_gbps": busbw(mb, dt_jit),
            "host_bridge_overhead_ms": round((dt - dt_jit) * 1e3, 3),
        })
        metrics[f"allreduce_ms_{mb}mb"] = _ms_metric(s_ar)
        metrics[f"reducescatter_ms_{mb}mb"] = _ms_metric(s_rs)
        metrics[f"broadcast_ms_{mb}mb"] = _ms_metric(s_bc)
        metrics[f"allreduce_async_ms_{mb}mb"] = _ms_metric(s_async)
        metrics[f"injit_psum_ms_{mb}mb"] = _ms_metric(s_jit)
    if hvd.rank() != 0:
        return None
    return {"size": hvd.size(), "results": results, "metrics": metrics}


def tpu_section(sizes_mb):
    """In-process, on the accelerator (single chip => size=1 identity
    semantics; measures each path's real per-call cost on TPU)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd

    hvd.init()
    dev = jax.devices()[0]
    results = []
    metrics = {}
    for mb in sizes_mb:
        n = int(mb * (1 << 20) / 4)
        x = np.ones((n,), np.float32)
        xd = jax.device_put(jnp.ones((n,), jnp.float32), dev)
        xd.block_until_ready()

        t_shim, s_shim = _timeit(lambda: hvd.allreduce(x))
        # device-resident fast path (jax.Array in, jax.Array out)
        t_dev, s_dev = _timeit(
            lambda: jax.block_until_ready(hvd.allreduce(xd)))
        t_rs, _s = _timeit(lambda: hvd.reducescatter(x, op=hvd.Sum))
        t_bc, _s = _timeit(lambda: hvd.broadcast(x, root_rank=0))
        # raw bridge each numpy-path call pays: H2D upload + D2H read.
        # D2H needs a FRESH device array per rep — jax.Array caches its
        # numpy value after the first conversion, so re-reading one
        # array times a host memcpy of the cache, not the transfer.
        t_h2d, _s = _timeit(
            lambda: jax.device_put(x, dev).block_until_ready())
        reps = 10
        fresh = [jax.device_put(xd + i, dev) for i in range(reps + 1)]
        jax.block_until_ready(fresh)
        np.asarray(fresh[-1])  # warm the conversion path itself
        t0 = time.perf_counter()
        for i in range(reps):
            np.asarray(fresh[i])
        t_d2h = (time.perf_counter() - t0) / reps

        p50, p99 = _pcts(s_shim)
        results.append({
            "size_mb": mb,
            "allreduce_numpy_ms": round(t_shim * 1e3, 3),
            "allreduce_numpy_ms_p50": p50,
            "allreduce_numpy_ms_p99": p99,
            "allreduce_device_resident_ms": round(t_dev * 1e3, 3),
            "reducescatter_numpy_ms": round(t_rs * 1e3, 3),
            "broadcast_numpy_ms": round(t_bc * 1e3, 3),
            "h2d_ms": round(t_h2d * 1e3, 3),
            "d2h_ms": round(t_d2h * 1e3, 3),
            "bridge_total_ms": round((t_h2d + t_d2h) * 1e3, 3),
        })
        metrics[f"allreduce_numpy_ms_{mb}mb"] = _ms_metric(s_shim)
        metrics[f"allreduce_device_ms_{mb}mb"] = _ms_metric(s_dev)
    return {
        "platform": dev.platform,
        "size": hvd.size(),
        "note": ("single chip: collective semantics are identity; "
                 "numbers are per-call path costs (dispatch + bridge), "
                 "not interconnect bandwidth"),
        "results": results,
        "metrics": metrics,
    }


def _append_history(out, bench):
    """One ledger line per run (driver side), from the per-op
    ``metrics`` the sections collect — the PR 7 regression ledger."""
    from sparkdl_tpu.observe import perf

    metrics = (out or {}).pop("metrics", None)
    if not metrics:
        return None
    rec = perf.history_record(
        metrics, device_kind=perf.device_kind(), bench=bench,
        extra={"gang_size": out.get("size")},
    )
    return perf.append_history(rec)


def main():
    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    if "--tpu" in sys.argv:
        out = tpu_section(sizes_mb=[1, 8, 64])
        history = _append_history(out, "allreduce_bench_tpu")
        print(json.dumps({"benchmark": "hvd_collectives_on_tpu",
                          "history": history, **out}))
        return
    np_arg = int(sys.argv[1]) if len(sys.argv) > 1 else -2
    from sparkdl import HorovodRunner

    out = HorovodRunner(np=np_arg).run(bench_main, sizes_mb=[1, 8, 64])
    history = _append_history(out, "allreduce_bench")
    print(json.dumps({"benchmark": "hvd_allreduce_bandwidth",
                      "history": history, **out}))


if __name__ == "__main__":
    main()
