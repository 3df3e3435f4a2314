#!/usr/bin/env python
"""Where does the headline step's time go? (VERDICT r4 item 7.)

Times the bench.py headline workload decomposed into nested programs —
forward loss, forward+backward, full train step — plus the two usual
suspects isolated at headline shapes (attention core, unembed+CE loss
tail), and captures a ``jax.profiler`` trace of three steps. The JSON
this prints next to the component numbers is the "5-line step
breakdown" BASELINE.md wants: optimizer = step − grad, backward =
grad − forward, and the isolated kernels say whether attention or the
loss tail dominates the forward.

Every measured loop is ONE jitted ``lax.scan`` with a host readback
(bench.py's discipline: per-step dispatch stays out of the
measurement). Components accumulate a scalar that depends
on every output so XLA cannot dead-code anything away.
"""

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# Peak constants live in the ONE per-device-kind table
# (sparkdl_tpu.observe.perf); this file's old v5e copy is gone. The
# breakdown document below uses perf.make_breakdown so the hand-rolled
# decomposition and the telemetry-derived attribution share one schema
# (cross-checkable in one file format), and every run appends to the
# same history.jsonl ledger the compare gate reads.
from sparkdl_tpu.observe import perf as _perf


def _timed(jit_fn, *args, n_steps):
    """Compile + warm, then time the second run; returns sec/step."""
    out = jit_fn(*args)
    _ = np.asarray(jax_leaf(out))
    t0 = time.perf_counter()
    out = jit_fn(*args)
    _ = np.asarray(jax_leaf(out))
    return (time.perf_counter() - t0) / n_steps


def jax_leaf(tree):
    import jax

    return jax.tree.leaves(tree)[0]


def main():
    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    import jax
    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models import Llama, LlamaConfig, lora_mask
    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.parallel.ring_attention import attention_reference
    from sparkdl_tpu.parallel.train import (
        make_lm_loss_fn,
        make_train_step,
    )

    tiny = bool(os.environ.get("SPARKDL_TPU_BENCH_TINY"))
    if tiny:
        cfg = LlamaConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, dtype=jnp.bfloat16, lora_rank=4)
        batch, seq, n_steps = 2, 128, 2
    else:
        cfg = LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=4096, dtype=jnp.bfloat16, lora_rank=16)
        batch, seq, n_steps = 8, 1024, 20
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    tokens = np.zeros((batch, seq), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    mask = lora_mask(params)
    opt = optax.masked(optax.adamw(1e-4), mask)
    opt_state = opt.init(params)
    loss_fn = make_lm_loss_fn(model)
    batch_data = {
        "inputs": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
        "targets": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
    }

    # 1. full train step ---------------------------------------------------
    step = make_train_step(loss_fn, opt, param_mask=mask)

    @functools.partial(jax.jit, donate_argnums=())
    def run_step(p, s, b):
        def body(carry, _):
            p_, s_ = carry
            p_, s_, m = step(p_, s_, b)
            return (p_, s_), m["loss"]

        (_, _), losses = jax.lax.scan(body, (p, s), None, length=n_steps)
        return losses[-1]

    t_step = _timed(run_step, params, opt_state, batch_data,
                    n_steps=n_steps)

    # 2. forward + backward (no optimizer) ---------------------------------
    @jax.jit
    def run_grad(p, b):
        def body(c, _):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            gsum = sum(jnp.sum(g.astype(jnp.float32))
                       for g in jax.tree.leaves(grads))
            return c + loss + gsum * 1e-9, None

        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=n_steps)
        return c

    t_grad = _timed(run_grad, params, batch_data, n_steps=n_steps)

    # 3. forward loss only --------------------------------------------------
    @jax.jit
    def run_fwd(p, b):
        def body(c, _):
            return c + loss_fn(p, b), None

        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=n_steps)
        return c

    t_fwd = _timed(run_fwd, params, batch_data, n_steps=n_steps)

    # 4. attention core at headline shapes (summed over layers) ------------
    head_dim = cfg.d_model // cfg.n_heads
    q = jnp.asarray(
        rng.standard_normal((batch, seq, cfg.n_heads, head_dim)),
        jnp.bfloat16)

    def attn_time(fn):
        @jax.jit
        def run(q_):
            def body(c, _):
                o = fn(q_, q_, q_)
                return c + jnp.sum(o.astype(jnp.float32)) * 1e-9, None

            c, _ = jax.lax.scan(
                body, jnp.float32(0.0), None,
                length=n_steps * cfg.n_layers)
            return c

        return _timed(run, q, n_steps=n_steps)  # sec per step (all layers)

    t_attn_ref = attn_time(functools.partial(attention_reference,
                                             causal=True))
    try:
        t_attn_flash = attn_time(functools.partial(flash_attention,
                                                   causal=True))
    except Exception as e:
        t_attn_flash = None
        sys.stderr.write(f"flash attention skipped: {e}\n")

    # 5. loss tail: unembed + CE at headline shapes ------------------------
    hidden = jnp.asarray(
        rng.standard_normal((batch, seq, cfg.d_model)), jnp.bfloat16)
    unembed = jnp.asarray(
        rng.standard_normal((cfg.d_model, cfg.vocab_size)) * 0.02,
        jnp.bfloat16)
    targets = batch_data["targets"]

    @jax.jit
    def run_tail(h, w, t):
        def body(c, _):
            logits = (h @ w).astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, t[..., None], axis=-1)[..., 0]
            return c + (logz - gold).mean(), None

        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=n_steps)
        return c

    t_tail = _timed(run_tail, hidden, unembed, targets, n_steps=n_steps)

    # 6. profiler trace of 3 steps (xplane; summarized here, the raw
    # trace stays in /tmp — MB-scale binaries don't belong in git) ---------
    trace_dir = os.environ.get("SPARKDL_TPU_TRACE_DIR",
                               "/tmp/sparkdl_trace_r5")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(3):
                _ = np.asarray(run_step(params, opt_state, batch_data))
        trace_note = f"xplane trace written to {trace_dir}"
    except Exception as e:
        trace_note = f"trace capture failed: {e}"

    tok_s = batch * seq / t_step
    device_kind = _perf.device_kind()
    # The same breakdown-document schema observe.perf derives from the
    # timeline — component axis differs (forward/backward/optimizer vs
    # compute/collective/...), the shape and sum-to-total contract are
    # identical, so both land in one file format and one ledger.
    breakdown = _perf.make_breakdown(
        t_step,
        {"forward": t_fwd,
         "backward": t_grad - t_fwd,
         "optimizer": t_step - t_grad},
        source="measured",
    )
    out = {
        "metric": "headline_step_breakdown",
        "platform": jax.devices()[0].platform,
        "device_kind": device_kind,
        "batch": batch, "seq": seq,
        "tokens_per_sec": round(tok_s, 1),
        "breakdown": breakdown,
        "ms": {
            "step": round(t_step * 1e3, 3),
            "forward": round(t_fwd * 1e3, 3),
            "backward": round((t_grad - t_fwd) * 1e3, 3),
            "optimizer": round((t_step - t_grad) * 1e3, 3),
            "attention_fwd_ref_all_layers": round(t_attn_ref * 1e3, 3),
            "attention_fwd_flash_all_layers": (
                round(t_attn_flash * 1e3, 3)
                if t_attn_flash is not None else None),
            "loss_tail_unembed_ce": round(t_tail * 1e3, 3),
        },
        "trace": trace_note,
    }
    _perf.append_history(_perf.history_record(
        {"headline_step_tokens_per_sec": {
            "value": round(tok_s, 1), "unit": "tokens/sec"},
         "headline_step_seconds": {
            "value": t_step, "unit": "s", "higher_is_better": False}},
        device_kind=device_kind, bench="step_breakdown.py",
        extra={"breakdown": breakdown},
    ))
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
