#!/usr/bin/env python
"""KV-cache decode throughput (tokens/sec) for the serving path: one
prefill + one scanned decode program (models/generate.py), for the
dense bf16 model AND the int8 weight-only variant (models/quant.py —
decode is HBM-bound, int8 halves the weight read). Prints one JSON
line per variant. Run on a TPU host; SPARKDL_TPU_BENCH_TINY=1 for a
CPU smoke.

Every record reports a RATE DISTRIBUTION over repeated timed runs —
``value`` is the p50 and ``tokens_per_sec_p99`` the slow tail (the
99th percentile of run latency, so p99 <= p50 by construction): a
single-shot number hides exactly the jitter (noisy neighbor, thermal
throttle, host GC) a p99 exposes.
"""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPS = 3


def _rate_fields(rates):
    """p50/p99 record fields from per-run tokens/sec samples. p99 is
    the SLOW tail: the rate at the 99th percentile of run latency =
    the 1st percentile of the rate samples (reciprocal is monotonic)."""
    import numpy as np

    return {
        "value": round(float(np.percentile(rates, 50)), 1),
        "tokens_per_sec_p50": round(float(np.percentile(rates, 50)), 1),
        "tokens_per_sec_p99": round(float(np.percentile(rates, 1)), 1),
        "reps": len(rates),
    }


def measure(model, params, prompt, new, batch, reps=REPS):
    """Per-run tokens/sec samples over ``reps`` timed runs (one warm
    run first so XLA compiles outside the measurement)."""
    import numpy as np

    from sparkdl_tpu.models.generate import generate

    # Warm (compiles prefill + decode_loop once).
    out = generate(model, params, prompt, max_new_tokens=new)
    np.asarray(out)

    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = generate(model, params, prompt, max_new_tokens=new)
        np.asarray(out)  # host readback = true sync
        rates.append(batch * new / (time.perf_counter() - t0))
    return rates


def main():
    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models import Llama, LlamaConfig
    from sparkdl_tpu.models.quant import quantize_llama_params

    if os.environ.get("SPARKDL_TPU_BENCH_TINY"):
        cfg = LlamaConfig.tiny(max_cache_len=128)
        batch, p_len, new = 2, 16, 32
    else:
        cfg = LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=4096, dtype=jnp.bfloat16,
            max_cache_len=2048,
        )
        batch, p_len, new = 8, 128, 512
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, p_len)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]

    # kernel=on|off label: whether the pallas kernel tier is engaged
    # for this record (observe.trend --metric can then render the
    # kernel trajectory once hardware shows up; on cpu every dispatch
    # resolves to the XLA fallback, so the label is "off")
    from sparkdl_tpu.ops._dispatch import use_pallas

    kernel_label = "on" if use_pallas() else "off"

    dense_fields = _rate_fields(measure(model, params, prompt, new, batch))
    tps = dense_fields["tokens_per_sec_p50"]
    print(json.dumps({
        "metric": "llama_decode_tokens_per_sec",
        **dense_fields,
        "unit": "tokens/sec",
        "batch": batch, "prompt_len": p_len, "new_tokens": new,
        "platform": jax.devices()[0].platform,
    }), flush=True)

    q_tree = quantize_llama_params(jax.tree.map(np.asarray, params))
    q_tree = jax.device_put(q_tree)  # keep the H2D upload out of the
    # timed run (the bf16 tree is already device-resident)
    cfg_q = dataclasses.replace(cfg, quant="int8")
    q_fields = _rate_fields(measure(Llama(cfg_q), q_tree, prompt, new, batch))
    tps_q = q_fields["tokens_per_sec_p50"]
    print(json.dumps({
        "metric": "llama_decode_int8_tokens_per_sec",
        "kernel": kernel_label,
        **q_fields,
        "unit": "tokens/sec",
        "batch": batch, "prompt_len": p_len, "new_tokens": new,
        "vs_bf16": round(tps_q / tps, 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # int4: quarter the weight bytes — group-wise scales, nibble
    # unpack in-kernel (decode is bytes-bound; this is the floor)
    q4_tree = jax.device_put(quantize_llama_params(
        jax.tree.map(np.asarray, params), bits=4))
    cfg_q4 = dataclasses.replace(cfg, quant="int4")
    q4_fields = _rate_fields(measure(Llama(cfg_q4), q4_tree, prompt, new, batch))
    tps_q4 = q4_fields["tokens_per_sec_p50"]
    print(json.dumps({
        "metric": "llama_decode_int4_tokens_per_sec",
        "kernel": kernel_label,
        **q4_fields,
        "unit": "tokens/sec",
        "batch": batch, "prompt_len": p_len, "new_tokens": new,
        "vs_bf16": round(tps_q4 / tps, 3),
        "vs_int8": round(tps_q4 / tps_q, 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # Speculative decoding: int8 draft proposing for the bf16 target —
    # greedy-exact output; the win is per-round (not per-token) host
    # dispatch plus the draft's halved HBM traffic.
    from sparkdl_tpu.models.speculative import speculative_generate

    k = 4
    spec_new = new
    _, _ = speculative_generate(   # warm: compiles all three programs
        model, params, q_tree, prompt, max_new_tokens=spec_new, k=k,
        draft_model=Llama(cfg_q))
    spec_rates = []
    for _ in range(REPS):
        t0 = time.perf_counter()
        out_s, stats = speculative_generate(
            model, params, q_tree, prompt, max_new_tokens=spec_new,
            k=k, draft_model=Llama(cfg_q))
        np.asarray(out_s)
        spec_rates.append(
            batch * spec_new / (time.perf_counter() - t0))
    spec_fields = _rate_fields(spec_rates)
    tps_spec = spec_fields["tokens_per_sec_p50"]
    print(json.dumps({
        "metric": "llama_decode_speculative_tokens_per_sec",
        **spec_fields,
        "unit": "tokens/sec",
        "k": k, "batch": batch, "new_tokens": spec_new,
        "acceptance_rate": round(
            stats["accepted"] / max(1, stats["proposed"]), 3),
        "rounds": stats["rounds"],
        "vs_plain_bf16": round(tps_spec / tps, 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # Continuous batching: a request stream with staggered lengths
    # through slot-mapped concurrent decode (models/serving.py) —
    # aggregate throughput + slot utilization. Single-stream serving
    # would run these sequentially, idling the chip between requests.
    from sparkdl_tpu.models.serving import ContinuousBatchingEngine

    if os.environ.get("SPARKDL_TPU_BENCH_TINY"):
        n_slots, chunk, reqs = 2, 8, [(12, 24), (8, 40), (16, 16),
                                      (10, 32)]
    else:
        n_slots, chunk = 8, 32
        reqs = [(64 + 16 * (i % 5), 128 + 64 * (i % 4))
                for i in range(24)]

    def build_engine(seed, page_size=0, paged_kernel="auto"):
        gen = np.random.default_rng(seed)
        m = model
        if paged_kernel != "auto":
            from sparkdl_tpu.models.llama import Llama

            m = Llama(dataclasses.replace(cfg, paged_kernel=paged_kernel))
        eng = ContinuousBatchingEngine(m, params, n_slots=n_slots,
                                       chunk=chunk, page_size=page_size)
        for p, nt in reqs:
            eng.submit(
                gen.integers(0, cfg.vocab_size, (p,)).astype(np.int32), nt
            )
        return eng

    def engine_rates(build, reps=REPS):
        """Repeated timed drains of the same request stream — a fresh
        engine per rep (compiled programs are cached module-level per
        config, so reps pay host scheduling + device time, the thing
        being measured). Returns (rates, last engine, total tokens)."""
        build(1).run()   # warm: compiles prefill buckets + chunk/round
        rates, eng, total = [], None, 0
        for _ in range(reps):
            eng = build(1)
            t0 = time.perf_counter()
            results = eng.run()
            dt = time.perf_counter() - t0
            total = sum(len(v) for v in results.values())
            rates.append(total / dt)
        return rates, eng, total

    cb_rates, eng, total_new = engine_rates(build_engine)
    cb_fields = _rate_fields(cb_rates)
    tps_cb = cb_fields["tokens_per_sec_p50"]
    print(json.dumps({
        "metric": "llama_decode_continuous_batching_tokens_per_sec",
        "kernel": kernel_label,
        **cb_fields,
        "unit": "tokens/sec",
        "n_slots": n_slots, "chunk": chunk, "requests": len(reqs),
        "generated_tokens": total_new,
        "slot_utilization": round(eng.stats["utilization"], 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # Speculative continuous batching: the same request stream with an
    # int8 draft proposing per slot — tokens identical, throughput
    # moves by acceptance_rate * (k+1) per target dispatch.
    from sparkdl_tpu.models.serving import SpeculativeBatchingEngine

    spec_k = 4

    def build_spec_engine(seed):
        gen = np.random.default_rng(seed)
        eng = SpeculativeBatchingEngine(
            model, params, q_tree, n_slots=n_slots, k=spec_k,
            draft_model=Llama(cfg_q))
        for p, nt in reqs:
            eng.submit(
                gen.integers(0, cfg.vocab_size, (p,)).astype(np.int32), nt
            )
        return eng

    sb_rates, eng_s, _total_s = engine_rates(build_spec_engine)
    sb_fields = _rate_fields(sb_rates)
    print(json.dumps({
        "metric": "llama_decode_spec_batching_tokens_per_sec",
        "kernel": kernel_label,
        **sb_fields,
        "unit": "tokens/sec",
        "n_slots": n_slots, "k": spec_k, "requests": len(reqs),
        "acceptance_rate": round(eng_s.stats["acceptance_rate"], 3),
        "rounds": eng_s.stats["rounds"],
        "vs_plain_engine": round(
            sb_fields["tokens_per_sec_p50"] / tps_cb, 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # Paged cache: same request stream through the pooled-page engine
    # — the dense-vs-paged throughput delta is the price of the
    # gather/scatter indirection (the payoff is pool-sized memory).
    page_size = 16 if os.environ.get("SPARKDL_TPU_BENCH_TINY") else 64

    pg_rates, eng_p, _ = engine_rates(
        lambda seed: build_engine(seed, page_size))
    pg_fields = _rate_fields(pg_rates)
    tps_pg = pg_fields["tokens_per_sec_p50"]
    print(json.dumps({
        "metric": "llama_decode_paged_tokens_per_sec",
        "kernel": ("on" if (use_pallas() and eng_p.cfg.paged_kernel != "off")
                   else "off"),
        **pg_fields,
        "unit": "tokens/sec",
        "n_slots": n_slots, "chunk": chunk, "page_size": page_size,
        "n_pages": eng_p.cfg.n_pages,
        "paged_kernel": eng_p.cfg.paged_kernel,
        "vs_dense_engine": round(tps_pg / tps_cb, 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # Same paged stream with the pallas kernel forced OFF: the delta
    # between this and the record above is the paged-attention
    # kernel's win over the gather path (only meaningful on TPU,
    # where "auto" uses the kernel).
    gt_fields = _rate_fields(engine_rates(
        lambda seed: build_engine(seed, page_size,
                                  paged_kernel="off"))[0])
    print(json.dumps({
        "metric": "llama_decode_paged_gather_tokens_per_sec",
        "kernel": "off",
        **gt_fields,
        "unit": "tokens/sec",
        "n_slots": n_slots, "chunk": chunk, "page_size": page_size,
        "vs_paged_auto": round(
            gt_fields["tokens_per_sec_p50"] / tps_pg, 3),
        "platform": jax.devices()[0].platform,
    }), flush=True)

    # Quant-matmul kernel A/B (ISSUE 19): the int8 engine with the
    # dequant GEMMs pinned to the XLA lowering (quant_kernel="off")
    # vs dispatched ("auto" — the pallas kernel on TPU, the identical
    # XLA fallback on cpu). Both legs land in the PR 7 ledger with
    # the SAME metric name, fallback first, so
    # ``observe.compare <history>@-2 <history>@-1`` gates the kernel
    # claim; on cpu the pair is identical programs and rc=0 proves
    # the gate wiring.
    from sparkdl_tpu.observe import perf

    def build_quant_engine(seed, quant_kernel):
        gen = np.random.default_rng(seed)
        eng = ContinuousBatchingEngine(
            model, params, n_slots=n_slots, chunk=chunk, quant="int8",
            quant_kernel=quant_kernel)
        for p, nt in reqs:
            eng.submit(
                gen.integers(0, cfg.vocab_size, (p,)).astype(np.int32), nt
            )
        return eng

    # Interleave the legs rep-by-rep (off, auto, off, auto, ...):
    # back-to-back blocks would fold slow host drift into the delta,
    # and >=5 samples per leg lets compare's rel-IQR noise threshold
    # engage instead of the bare 5% floor.
    for mode in ("off", "auto"):
        build_quant_engine(1, mode).run()   # warm both programs
    qk_samples = {"off": [], "auto": []}
    for _ in range(5):
        for mode in ("off", "auto"):
            eng = build_quant_engine(1, mode)
            t0 = time.perf_counter()
            results = eng.run()
            dt = time.perf_counter() - t0
            total = sum(len(v) for v in results.values())
            qk_samples[mode].append(total / dt)

    for label, leg, mode in (("off", "fallback", "off"),
                             ("on", "kernel", "auto")):
        met = perf.sample_metric(qk_samples[mode], unit="tokens/sec",
                                 higher_is_better=True)
        perf.append_history(perf.history_record(
            {"engine_int8_tokens_per_sec": met},
            device_kind=perf.device_kind(),
            bench=f"decode_bench:{leg}",
            extra={"kernel": label, "quant_kernel": mode}))
        print(json.dumps({
            "metric": "llama_decode_int8_engine_tokens_per_sec",
            "kernel": label,
            "quant_kernel": mode,
            **_rate_fields(qk_samples[mode]),
            "unit": "tokens/sec",
            "n_slots": n_slots, "chunk": chunk, "requests": len(reqs),
            "platform": jax.devices()[0].platform,
        }), flush=True)


if __name__ == "__main__":
    main()
