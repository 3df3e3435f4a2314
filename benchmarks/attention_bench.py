#!/usr/bin/env python
"""Attention benchmarks: flash kernel vs XLA reference across sequence
lengths, plus the ring-attention overlap-vs-serialized schedule pair
(ISSUE 10). Timing uses one jitted scan + host readback, so that
per-call dispatch stays out of the measured time.

Every metric reports ``p50``/``p99`` over ``REPS`` timed invocations
and the run appends schema-versioned lines to the PR 7 ledger
(``benchmarks/results/history.jsonl``): the combined
``attention_bench`` record, then a kernel-vs-fallback A/B pair
(``attention_bench:fallback`` / ``attention_bench:kernel``, same
metric names) so ``python -m sparkdl_tpu.observe.compare`` can gate
the kernel claim directly — not a one-off stdout line.

``--tiny`` (or ``SPARKDL_TPU_BENCH_TINY=1``) shrinks shapes for smoke
runs on deviceless hosts.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import numpy as np

REPS = 5


def timed(fn, q, n_steps=10, reps=REPS):
    """One ledger metric (ms/step, ``perf.sample_metric`` shape) over
    ``reps`` timed invocations of a jitted ``n_steps`` scan."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.observe import perf

    @jax.jit
    def many(q):
        def body(c, _):
            o = fn(q, q, q)
            return c + o[0, 0, 0, 0].astype(jnp.float32), None

        out, _ = jax.lax.scan(body, 0.0, None, length=n_steps)
        return out

    _ = np.asarray(many(q))        # compile
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        _ = np.asarray(many(q))
        samples.append((time.perf_counter() - t0) / n_steps * 1e3)
    return perf.sample_metric(samples, unit="ms")


def kernel_section(seqs, tiny):
    import jax.numpy as jnp

    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.parallel.ring_attention import attention_reference

    rng = np.random.RandomState(0)
    rows, metrics = [], {}
    for s in seqs:
        b = max(1, (1024 if tiny else 8192) // s)
        h, d = (2, 32) if tiny else (8, 128)
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        flash = timed(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True), q)
        xla = timed(
            lambda q_, k_, v_: attention_reference(q_, k_, v_, causal=True),
            q)
        rows.append({
            "seq": s,
            "flash_ms_p50": flash["p50"], "flash_ms_p99": flash["p99"],
            "xla_ms_p50": xla["p50"], "xla_ms_p99": xla["p99"],
            "speedup": (round(xla["p50"] / flash["p50"], 2)
                        if flash["p50"] else None),
        })
        metrics[f"flash_ms_s{s}"] = flash
        metrics[f"xla_ms_s{s}"] = xla
    return rows, metrics


def ab_section(seqs, tiny, kernel_interpret=False):
    """Kernel-vs-fallback A/B pair (ISSUE 19): the KERNEL leg runs
    ``flash_attention`` as dispatched — the pallas kernel on TPU, the
    XLA reference fallback on cpu, so the cpu pair proves the compare
    gate's wiring (identical programs, rc=0 by construction) and the
    TPU pair carries the real claim. The FALLBACK leg pins
    ``attention_reference`` explicitly. Both legs land as separate
    ledger records with the SAME metric names (``attn_ms_s{seq}``), so
    ``observe.compare <history>@-2 <history>@-1`` gates kernel vs
    fallback directly.

    ``kernel_interpret`` (off-TPU only) forces the kernel leg through
    the interpret-mode emulation instead of the dispatch fallback —
    the autotuner's cpu search mode: tile knobs change the emulated
    program, so a tile trial measures SOMETHING tile-shaped on a
    deviceless host. Never the default: emulation timings must not
    pollute the gated kernel-vs-fallback rows."""
    import jax.numpy as jnp

    from sparkdl_tpu.ops._dispatch import use_pallas
    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.parallel.ring_attention import attention_reference

    interpret = True if (kernel_interpret and not use_pallas()) else None
    n_steps = 2 if interpret else 10
    rng = np.random.RandomState(2)
    rows, kernel_metrics, fallback_metrics = [], {}, {}
    for s in seqs:
        b = max(1, (1024 if tiny else 8192) // s)
        h, d = (2, 32) if tiny else (8, 128)
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        kern = timed(
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True, interpret=interpret),
            q, n_steps=n_steps)
        fall = timed(
            lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=True),
            q, n_steps=n_steps)
        kernel_metrics[f"attn_ms_s{s}"] = kern
        fallback_metrics[f"attn_ms_s{s}"] = fall
        rows.append({
            "seq": s,
            "kernel_ms_p50": kern["p50"],
            "fallback_ms_p50": fall["p50"],
        })
    return rows, kernel_metrics, fallback_metrics


def ring_section(tiny):
    """Overlap-vs-serialized ring schedules on a (1, N)-device mesh —
    the before/after pair for the ISSUE 10 hop restructure. On a
    single-chip/CPU host this measures the schedule's compute cost
    (the wire win needs a real interconnect); the ledger row keeps the
    trajectory either way."""
    import jax

    n = min(4, jax.device_count())
    if n < 2:
        return None, {}
    from functools import partial

    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from sparkdl_tpu.parallel.ring_attention import ring_self_attention

    mesh = Mesh(np.array(jax.devices()[:n]).reshape(1, n),
                ("data", "seq"))
    spec = P("data", "seq", None, None)
    rng = np.random.RandomState(1)
    b, s, h, d = (2, 64 * n, 2, 16) if tiny else (4, 512 * n, 4, 64)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    rows, metrics = [], {}
    out = {}
    for name, overlap in (("overlap", True), ("serialized", False)):
        ring = jax.jit(jax.shard_map(
            partial(ring_self_attention, axis_name="seq", causal=True,
                    overlap=overlap),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        ))
        met = timed(ring, q, n_steps=4)
        out[name] = np.asarray(ring(q, q, q))
        rows.append({"schedule": name, "ring_ms_p50": met["p50"],
                     "ring_ms_p99": met["p99"]})
        metrics[f"ring_{name}_ms"] = met
    return {
        "devices": n, "seq": s,
        "bit_exact": bool(np.array_equal(out["overlap"],
                                         out["serialized"])),
        "rows": rows,
    }, metrics


def main():
    tiny = ("--tiny" in sys.argv
            or os.environ.get("SPARKDL_TPU_BENCH_TINY", "") not in ("", "0"))
    from sparkdl_tpu.observe import perf

    kernel_interpret = "--kernel-interpret" in sys.argv

    seqs = (256, 512) if tiny else (1024, 2048, 4096, 8192)
    rows, metrics = kernel_section(seqs, tiny)
    ring, ring_metrics = ring_section(tiny)
    metrics.update(ring_metrics)
    record = perf.history_record(
        metrics, device_kind=perf.device_kind(), bench="attention_bench")
    history = perf.append_history(record)

    # kernel-vs-fallback A/B pair: two records, same metric names,
    # fallback first so `<history>@-2 <history>@-1` is fallback→kernel
    ab_rows, kernel_metrics, fallback_metrics = ab_section(
        seqs, tiny, kernel_interpret=kernel_interpret)
    perf.append_history(perf.history_record(
        fallback_metrics, device_kind=perf.device_kind(),
        bench="attention_bench:fallback", extra={"kernel": "off"}))
    perf.append_history(perf.history_record(
        kernel_metrics, device_kind=perf.device_kind(),
        bench="attention_bench:kernel",
        extra={"kernel": "on",
               "kernel_interpret": bool(kernel_interpret)}))

    print(json.dumps({
        "benchmark": "flash_attention_vs_xla",
        "tiny": tiny,
        "rows": rows,
        "ab": ab_rows,
        "kernel_interpret": kernel_interpret,
        "ring": ring,
        "history": history,
    }))


if __name__ == "__main__":
    main()
