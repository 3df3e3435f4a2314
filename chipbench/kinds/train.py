"""Kind ``train``: a LoRA fine-tune through ``HorovodRunner(np=chips)``.

The parent never imports JAX: the launcher's real worker holds the
chip, measures, traces and checks there, and returns rank 0's numbers.
The job is the ``chip_smoke.train_main`` recipe: float32 adapters on a
frozen bf16 base, ``make_train_step(param_mask=...)`` jitted with
donated state, a ring of seeded batches made before the window.
"""

import math
import os
import time

from chipbench.common import (
    CompileCounter,
    NoChip,
    cache_everything,
    device_facts,
    init_params,
    llama_config,
    require_chips,
)


def setup(config, job, seed):
    """(cfg, params, mask, loss_fn, step) of the job, un-jitted: shared
    by the chip run, the CPU rehearsal and the sandbox compile."""
    import optax

    from sparkdl_tpu.models import Llama, lora_mask
    from sparkdl_tpu.parallel.train import make_lm_loss_fn, make_train_step

    cfg = llama_config(
        config, lora_rank=job["lora_rank"], lora_alpha=job["lora_alpha"],
        lora_targets=tuple(job["lora_targets"]),
        attention=job["attention"], remat=job["remat"])
    params = init_params(cfg, seed, keep_f32=lambda path: "lora_" in path)
    mask = lora_mask(params)
    loss_fn = make_lm_loss_fn(Llama(cfg), loss=job["loss"],
                              chunk=job["loss_chunk"], ce_bf16=True)
    opt = optax.masked(optax.adamw(job["lr"]), mask)
    return cfg, params, mask, loss_fn, opt, make_train_step(
        loss_fn, opt, param_mask=mask)


def adapter_loss_and_grads(loss_fn, mask):
    """``f(params, batch) -> (loss, adapter gradients)``, differentiated
    in the adapters only: a gradient tree over the frozen base would be
    7.5 GB of zeros."""
    import jax

    def f(params, batch):
        flat, treedef = jax.tree.flatten(params)
        keep = treedef.flatten_up_to(mask)

        def loss_of(trainable):
            it = iter(trainable)
            return loss_fn(treedef.unflatten(
                [next(it) if k else jax.lax.stop_gradient(p)
                 for p, k in zip(flat, keep)]), batch)

        return jax.value_and_grad(loss_of)(
            [p for p, k in zip(flat, keep) if k])

    return f


def reference_check(config, job, params, batch, loss_fn, mask):
    """The system's loss and adapter-gradient norm on ONE seeded
    sequence against the plain reference's, with the tolerances the
    traffic file states."""
    import jax
    import optax

    from chipbench import reference

    one = {k: v[:1] for k, v in batch.items()}
    loss, grads = jax.jit(adapter_loss_and_grads(loss_fn, mask))(params, one)
    got = float(loss), float(optax.global_norm(grads))
    want = reference.loss_and_adapter_grad_norm(
        params, one["inputs"], one["targets"],
        reference.arch_of(config, job["lora_alpha"], job["lora_rank"]))
    rel = [abs(g - w) / abs(w) for g, w in zip(got, want)]
    tol = job["check"]
    return {"loss": got[0], "ref_loss": want[0], "loss_rel": rel[0],
            "grad_norm": got[1], "ref_grad_norm": want[1],
            "grad_norm_rel": rel[1],
            "ok": rel[0] <= tol["loss_rtol"] and rel[1] <= tol["grad_norm_rtol"]}


def measure(step, state, batches, done):
    """Steps, each ended by reading its loss, until ``done(seconds so
    far, steps so far)``. Returns (state, losses, seconds the steps
    took)."""
    import jax

    losses, t0 = [], time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("chipbench.step"):
            *state, metrics = step(*state, batches[len(losses) % len(batches)])
            losses.append(float(jax.block_until_ready(metrics["loss"])))
        elapsed = time.perf_counter() - t0
        if done(elapsed, len(losses)):
            return state, losses, elapsed


def gang_step(loss_fn, mask, opt, params, opt_state, batch):
    """The data-parallel step as Horovod users write it (the
    ``chip_smoke.gang_main`` pattern): one program for loss and adapter
    gradients, ``hvd.grouped_allreduce(op=Average)`` BETWEEN programs,
    one program for the update. Returns (step, seconds to compile both,
    kernels in the gradient program, its memory analysis)."""
    import jax

    import sparkdl_tpu.hvd as hvd

    def update(params, opt_state, grads):
        flat, treedef = jax.tree.flatten(params)
        keep = treedef.flatten_up_to(mask)
        it = iter(grads)
        full = treedef.unflatten(
            [next(it) if k else p for p, k in zip(flat, keep)])
        updates, opt_state = opt.update(full, opt_state, params)
        new = [p + u if k else p for p, u, k in zip(
            flat, treedef.flatten_up_to(updates), keep)]
        return treedef.unflatten(new), opt_state

    t0 = time.perf_counter()
    grads_fn = jax.jit(adapter_loss_and_grads(loss_fn, mask)).lower(
        params, batch).compile()
    _, grad_shapes = jax.eval_shape(
        adapter_loss_and_grads(loss_fn, mask), params, batch)
    update_fn = jax.jit(update, donate_argnums=(0, 1)).lower(
        params, opt_state, grad_shapes).compile()
    compile_s = time.perf_counter() - t0

    def step(params, opt_state, batch):
        loss, grads = grads_fn(params, batch)
        grads = hvd.grouped_allreduce(grads, op=hvd.Average)
        params, opt_state = update_fn(params, opt_state, grads)
        return params, opt_state, {"loss": loss}

    return (step, compile_s, grads_fn.as_text().count("tpu_custom_call"),
            grads_fn.memory_analysis())


def train_job(spec, seed, seconds, trace):
    """Runs in the gang's worker, which holds the chip."""
    entered = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd
    from chipbench import trace_reduce
    from sparkdl_tpu.parallel.train import global_batch

    hvd.init()
    require_chips(jax, spec["cell"]["chips"])
    cache_everything()
    counter = CompileCounter()
    job, config = spec["traffic"], spec["config"]
    annotate = jax.profiler.TraceAnnotation

    cfg, params, mask, loss_fn, opt, step = setup(config, job, seed)
    opt_state = opt.init(params)
    rng = np.random.default_rng([seed, hvd.rank()])   # each rank its own
    batches = [jax.tree.map(jnp.asarray, global_batch(
        rng, cfg.vocab_size, job["batch"], job["seq"]))
        for _ in range(job["ring"])]
    tokens_per_step = job["batch"] * job["seq"]

    agree = lambda done: done
    if hvd.size() > 1:
        compiled, compile_s, kernels, memory = gang_step(
            loss_fn, mask, opt, params, opt_state, batches[0])
        # every rank stops after the same step: one says so, all do
        agree = lambda done: float(hvd.allreduce(
            jnp.float32(done), op=hvd.Sum)) > 0
    else:
        t0 = time.perf_counter()
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
            params, opt_state, batches[0]).compile()
        compile_s = time.perf_counter() - t0
        kernels = compiled.as_text().count("tpu_custom_call")
        memory = compiled.memory_analysis()

    state = (params, opt_state)
    with annotate("chipbench.warm_up"):
        for i in range(job["warmup_steps"]):
            *state, metrics = compiled(*state, batches[i % len(batches)])
        jax.block_until_ready(metrics["loss"])
        agree(False)                # a gang's stop vote is a program too

    # the profiler's own start-up stays outside the measured stretch: a
    # traced run measures its rate first, untraced, then traces a few steps
    untraced = max(seconds - job["traced_reserve_s"], seconds / 2) \
        if trace else seconds
    window_started, t_window = time.time(), time.perf_counter()
    state, losses, elapsed = measure(
        compiled, state, batches, lambda s, n: agree(s >= untraced))
    compiles = counter.since(t_window)
    reduced = None
    if trace:
        trace_dir = os.path.join(spec["out_dir"], "trace")
        with trace_reduce.profile(trace_dir):
            state, _, _ = measure(compiled, state, batches,
                                  lambda s, n: n >= job["traced_steps"])
        reduced = trace_reduce.reduce_dir(trace_dir)

    with annotate("chipbench.reference_check"):
        check = reference_check(
            config, job, state[0], batches[0], loss_fn, mask)
    return {
        "entered": entered, "window_started": window_started,
        "steps": len(losses), "elapsed_s": elapsed, "losses": losses,
        "tokens_per_step": tokens_per_step, "compile_s": compile_s,
        "tpu_custom_calls": kernels, "compiles_in_window": compiles,
        "check": check, "device": device_facts(jax), "trace": reduced,
        "step_program_bytes": {
            "arguments": memory.argument_size_in_bytes,
            "temporaries": memory.temp_size_in_bytes,
            "outputs": memory.output_size_in_bytes,
            "aliased": memory.alias_size_in_bytes},
    }


def summarize(spec, out, launched):
    """The harness's view of what the worker returned."""
    finite = [math.isfinite(x) for x in out["losses"]]
    chips = spec["cell"]["chips"]
    notes = [{**{k: out[k] for k in (
        "steps", "elapsed_s", "compile_s", "tpu_custom_calls",
        "compiles_in_window", "check", "step_program_bytes")},
        "first_losses": out["losses"][:3], "last_losses": out["losses"][-3:]}]
    if out["trace"]:
        notes.append({"trace_layout": out["trace"].pop("layout"),
                      "device_modules": out["trace"]["device_modules"]})
    run = {
        "correct": (all(finite) and out["check"]["ok"]
                    and out["compiles_in_window"] == 0
                    and out["tpu_custom_calls"]
                    >= spec["traffic"]["min_kernels"]),
        "attempted": out["steps"], "failed": finite.count(False),
        "end_to_end": {
            "setup_s": out["window_started"] - spec["started"],
            "train_tokens_per_s_per_chip":
                out["steps"] * out["tokens_per_step"] / out["elapsed_s"]},
        "device": out["device"], "notes": notes,
        "launch_s": out["entered"] - launched, "compile_s": out["compile_s"],
        "chips": chips, "trace": out["trace"], "spec": spec,
    }
    if out["trace"]:
        run["device"] = {**out["device"], "busy_s": out["trace"]["busy_s"],
                         "window_s": out["trace"]["window_s"]}
        run["breakdown"] = {
            "device_ops": ([["module " + m, s] for m, s in
                            out["trace"]["device_modules"][:3]]
                           + out["trace"]["device_ops"])[:10],
            "idle_gaps": out["trace"]["idle_gaps"]}
    return run


def run(spec, *, seed, seconds, trace):
    from sparkdl import HorovodRunner
    from sparkdl_tpu.horovod import launcher

    chips = spec["cell"]["chips"]
    # what is attached, asked of a child that exits before any worker
    # starts (the launcher's own slot probe; it keeps the answer)
    launched = time.time()
    local = launcher.probe_local_devices(
        os.environ.get(launcher.WORKER_PLATFORM_ENV))
    if local.platform != "tpu" or local.count != chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{local.count} {local.platform!r} device(s)")
    out = HorovodRunner(np=chips).run(
        train_job, spec=spec, seed=seed, seconds=seconds, trace=trace)
    return summarize(spec, out, launched)
