#!/usr/bin/env python
"""Model-family throughput benchmarks (BASELINE.json configs 2 and 3):

- ResNet-50 / ImageNet-shape training, samples/sec/chip
- BERT-base / SQuAD-shape (seq 384) fine-tune training, samples/sec/chip

The reference publishes no numbers for these (BASELINE.md); the point
of this file is to RECORD the per-chip scale-out unit on real TPU
hardware next to an analytic model-FLOPs figure, the same way bench.py
does for the Llama-LoRA flagship. One JSON line per config.

Measurement pattern matches bench.py: the whole measured loop is ONE
jitted ``lax.scan`` over steps with donated carries, synced by a host
readback.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import functools

import numpy as np

# MFU denominators come from the ONE per-device-kind peak table
# (sparkdl_tpu.observe.perf; SPARKDL_TPU_PEAK_FLOPS still overrides),
# keyed off the probed device kind (perf.device_kind) instead of a
# hard-coded v5e copy.
from sparkdl_tpu.observe import perf as _perf


def _measure_scan(step, carry, batch_data, n_steps):
    """Compile + warm one scan program, then time a second pass."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_n(carry, b):
        def body(c, _):
            c, loss = step(c, b)
            return c, loss

        carry, losses = jax.lax.scan(body, carry, None, length=n_steps)
        return carry, losses[-1]

    carry, last = run_n(carry, batch_data)
    _ = np.asarray(last)
    t0 = time.perf_counter()
    carry, last = run_n(carry, batch_data)
    last = float(np.asarray(last))
    dt = time.perf_counter() - t0
    assert np.isfinite(last)
    return dt, last


def bench_resnet50(batch=128, image=224, n_steps=10):
    import jax
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.resnet import ResNet50

    model = ResNet50()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, image, image, 3)),
                    jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.1, momentum=0.9)
    opt_state = opt.init(params)

    from sparkdl_tpu.parallel.train import cross_entropy_loss

    def loss_fn(p, bs, xb, yb):
        logits, new = model.apply(
            {"params": p, "batch_stats": bs}, xb, train=True,
            mutable=["batch_stats"],
        )
        return cross_entropy_loss(logits, yb), new["batch_stats"]

    def step(carry, b):
        p, bs, s = carry
        (loss, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, bs, b["x"], b["y"]
        )
        updates, s = opt.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return (p, bs, s), loss

    dt, last = _measure_scan(
        step, (params, batch_stats, opt_state), {"x": x, "y": y}, n_steps
    )
    sps = n_steps * batch / dt
    # ResNet-50 @224: ~4.09 GFLOP forward/sample; x3 for fwd+bwd.
    model_flops = 3 * 4.09e9 * sps
    kind = _perf.device_kind()
    return {
        "metric": "resnet50_train_samples_per_sec_per_chip",
        "value": round(sps, 1), "unit": "samples/sec/chip",
        "batch": batch, "image": image,
        "device_kind": kind,
        "model_tflops_per_sec": round(model_flops / 1e12, 1),
        "mfu": round(model_flops / _perf.peak_flops(kind), 4),
        "last_loss": round(last, 4),
    }


def bench_bert_squad(batch=32, seq=384, n_steps=10):
    import jax
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.bert import BertConfig, BertForQuestionAnswering

    cfg = BertConfig.base()
    model = BertForQuestionAnswering(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    types = jnp.zeros((batch, seq), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.bool_)
    starts = jnp.asarray(rng.integers(0, seq, (batch,)), jnp.int32)
    ends = jnp.asarray(rng.integers(0, seq, (batch,)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids[:2], types[:2],
                        mask[:2])["params"]
    opt = optax.adamw(3e-5)
    opt_state = opt.init(params)

    from sparkdl_tpu.parallel.train import cross_entropy_loss

    def loss_fn(p, b):
        start, end = model.apply({"params": p}, b["ids"], b["types"],
                                 b["mask"])
        return (cross_entropy_loss(start, b["starts"])
                + cross_entropy_loss(end, b["ends"]))

    def step(carry, b):
        p, s = carry
        loss, grads = jax.value_and_grad(loss_fn)(p, b)
        updates, s = opt.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        return (p, s), loss

    dt, last = _measure_scan(
        step, (params, opt_state),
        {"ids": ids, "types": types, "mask": mask, "starts": starts,
         "ends": ends},
        n_steps,
    )
    sps = n_steps * batch / dt
    # BERT-base: ~85M non-embedding matmul params -> 2N fwd FLOPs/token
    # + QK^T/AV attention; x3 for fwd+bwd (full fine-tune trains all).
    n_matmul = 85.1e6
    attn = cfg.n_layers * 4 * seq * cfg.d_model
    flops_per_token = 3 * (2 * n_matmul + attn)
    model_flops = flops_per_token * sps * seq
    kind = _perf.device_kind()
    return {
        "metric": "bert_base_squad_train_samples_per_sec_per_chip",
        "value": round(sps, 1), "unit": "samples/sec/chip",
        "batch": batch, "seq": seq,
        "device_kind": kind,
        "model_tflops_per_sec": round(model_flops / 1e12, 1),
        "mfu": round(model_flops / _perf.peak_flops(kind), 4),
        "last_loss": round(last, 4),
    }


def main():
    # Same switch as bench.py: CI smoke runs name their platform,
    # before this process first touches a device.
    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    if os.environ.get("SPARKDL_TPU_BENCH_TINY"):
        jobs = [functools.partial(bench_resnet50, batch=4, image=32,
                                  n_steps=2),
                functools.partial(bench_bert_squad, batch=2, seq=64,
                                  n_steps=2)]
    else:
        jobs = [bench_resnet50, bench_bert_squad]
    for job in jobs:
        try:
            rec = job()
            _perf.append_history(_perf.history_record(
                {rec["metric"]: {"value": rec["value"],
                                 "unit": rec["unit"]}},
                device_kind=rec.get("device_kind"),
                bench="model_bench.py",
                extra={"mfu": rec.get("mfu")},
            ))
            print(json.dumps(rec), flush=True)
        except Exception as e:  # keep sweeping on OOM etc.
            print(json.dumps({"error": str(e)[:300]}), flush=True)


if __name__ == "__main__":
    main()
