"""Kind ``train_afmoe``: a LoRA fine-tune of one pipeline stage of a
decoder that mixes window and full attention layers over gated routed
experts (``models.HybridDecoder`` with the letters ``S``, ``F``, ``D``,
``G``: Trinity-Mini's ``afmoe``) through ``HorovodRunner(np=1)``.

``kinds/train_mla.py``'s job with a reference and a count of required
work of its own (``reference_afmoe.py``, ``flops_afmoe.py``): the
launcher, the builder, the loss, the step, the window, the seeded
weights and the by-scope table are the same functions. The chip holds
every routed expert of its layers, so every (token, pick) pair lands
here. A traced run switches the program's telemetry on for its worker
and brings back what ``flash.tiles`` counted while the step was built.
"""

import importlib.util
import os
import tempfile
import time

from chipbench import trace_reduce
from chipbench.common import (
    CompileCounter,
    NoChip,
    cache_everything,
    device_facts,
    require_chips,
)
from chipbench.kinds.train import adapter_loss_and_grads, measure, summarize
from chipbench.kinds.train_hybrid import routing, scope_table
from chipbench.kinds.train_mla import setup

WINDOWED = "sparkdl_tpu.models.mixed_attention"


def reference_check(config, job, cfg, params, batch, loss_fn, mask):
    """The system's loss and adapter-gradient norm on ONE seeded
    sequence against the plain reference's, with the tolerances the
    traffic file states; and, as readings, the share of (token, pick)
    pairs in which the program's bf16 layers chose another expert than
    the float32 reference, and the experts' load."""
    import jax
    import numpy as np
    import optax

    from chipbench import reference_afmoe

    started = time.perf_counter()
    one = {k: v[:1] for k, v in batch.items()}
    loss, grads = jax.jit(adapter_loss_and_grads(loss_fn, mask))(params, one)
    got = float(loss), float(optax.global_norm(grads))
    chose = routing(cfg, params, one["inputs"])
    *want, ref_chose = reference_afmoe.loss_and_adapter_grad_norm(
        params, one["inputs"], one["targets"],
        reference_afmoe.arch_of(config, job["lora_alpha"], job["lora_rank"]))
    rel = [abs(g - w) / abs(w) for g, w in zip(got, want)]
    differ = {}
    for layer, ref_idx in ref_chose.items():
        idx = np.asarray(chose[layer]["picks"])
        ref_idx = np.asarray(ref_idx).reshape(idx.shape)
        differ[layer] = float(np.mean(
            ~(idx[:, :, None] == ref_idx[:, None, :]).any(-1)))
    counts = np.stack([np.asarray(c["expert_counts"])
                       for c in chose.values()])
    tol = job["check"]
    return {"loss": got[0], "ref_loss": want[0], "loss_rel": rel[0],
            "grad_norm": got[1], "ref_grad_norm": want[1],
            "grad_norm_rel": rel[1],
            "picks_differ_share": differ,
            "rows_here": counts.sum(1).tolist(),
            "expert_load_max_over_mean": float(
                (counts.max(1) / counts.mean(1)).max()),
            "seconds": time.perf_counter() - started,
            "ok": rel[0] <= tol["loss_rtol"] and rel[1] <= tol["grad_norm_rtol"]}


def counted(name):
    """What the program's counter `name` holds in this process: one
    ``{**labels, "count": n}`` a label set; empty with telemetry off."""
    from sparkdl_tpu import observe

    return [{**c["labels"], "count": c["value"]}
            for c in observe.metrics().snapshot()["counters"]
            if c["name"] == name]


def train_job(spec, seed, seconds, trace):
    """Runs in the launcher's worker, which holds the chip."""
    entered = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.parallel.train import global_batch

    hvd.init()
    require_chips(jax, spec["cell"]["chips"])
    cache_everything()
    counter = CompileCounter()
    job, config = spec["traffic"], spec["config"]
    annotate = jax.profiler.TraceAnnotation

    cfg, params, mask, loss_fn, opt, step = setup(config, job, seed)
    opt_state = opt.init(params)
    rng = np.random.default_rng([seed, hvd.rank()])
    batches = [jax.tree.map(jnp.asarray, global_batch(
        rng, cfg.vocab_size, job["batch"], job["seq"]))
        for _ in range(job["ring"])]

    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt_state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    hlo_text = compiled.as_text()
    memory = compiled.memory_analysis()
    # the step's kernels were built just now: which tiles they walk
    flash_tiles = counted("flash.tiles")

    state = (params, opt_state)
    with annotate("chipbench.warm_up"):
        for i in range(job["warmup_steps"]):
            *state, metrics = compiled(*state, batches[i % len(batches)])
        jax.block_until_ready(metrics["loss"])

    # the profiler's own start-up stays outside the measured stretch: a
    # traced run measures its rate first, untraced, then traces a few steps
    untraced = max(seconds - job["traced_reserve_s"], seconds / 2) \
        if trace else seconds
    window_started, t_window = time.time(), time.perf_counter()
    state, losses, elapsed = measure(
        compiled, state, batches, lambda s, n: s >= untraced)
    compiles = counter.since(t_window)
    reduced = by_scope = None
    if trace:
        trace_dir = os.path.join(spec["out_dir"], "trace")
        with trace_reduce.profile(trace_dir):
            state, _, _ = measure(compiled, state, batches,
                                  lambda s, n: n >= job["traced_steps"])
        by_scope = scope_table(trace_dir, hlo_text, job["traced_steps"])
        reduced = trace_reduce.reduce_dir(trace_dir)

    with annotate("chipbench.reference_check"):
        check = reference_check(
            config, job, cfg, state[0], batches[0], loss_fn, mask)
    return {
        "entered": entered, "window_started": window_started,
        "steps": len(losses), "elapsed_s": elapsed, "losses": losses,
        "tokens_per_step": job["batch"] * job["seq"],
        "compile_s": compile_s,
        "tpu_custom_calls": hlo_text.count("tpu_custom_call"),
        "compiles_in_window": compiles,
        "check": check, "device": device_facts(jax), "trace": reduced,
        "by_scope": by_scope, "flash_tiles": flash_tiles,
        "step_program_bytes": {
            "arguments": memory.argument_size_in_bytes,
            "temporaries": memory.temp_size_in_bytes,
            "outputs": memory.output_size_in_bytes,
            "aliased": memory.alias_size_in_bytes},
    }


def run(spec, *, seed, seconds, trace):
    # before the launcher starts anything: the parent commit of PR 35
    # has no window in its attention, and says so at once
    if importlib.util.find_spec(WINDOWED) is None:
        raise SystemExit("chipbench: this checkout's program has no "
                         f"windowed attention ({WINDOWED})")
    from sparkdl import HorovodRunner
    from sparkdl_tpu.horovod import launcher
    from sparkdl_tpu.observe import TELEMETRY_DIR_ENV

    chips = spec["cell"]["chips"]
    launched = time.time()
    local = launcher.probe_local_devices(
        os.environ.get(launcher.WORKER_PLATFORM_ENV))
    if local.platform != "tpu" or local.count != chips or chips != 1:
        raise NoChip(f"the cell needs {chips} TPU chip(s), and this kind "
                     f"runs one chip's stage; JAX finds {local.count} "
                     f"{local.platform!r} device(s)")
    if trace and not os.environ.get(TELEMETRY_DIR_ENV):
        # the program counts only with its telemetry on; what that
        # writes besides is not the benchmark's to keep
        os.environ[TELEMETRY_DIR_ENV] = tempfile.mkdtemp(
            prefix="chipbench-telemetry-")
    out = HorovodRunner(np=chips).run(
        train_job, spec=spec, seed=seed, seconds=seconds, trace=trace)
    run = summarize(spec, out, launched)
    run.update({k: out[k] for k in ("check", "by_scope", "flash_tiles")})
    if out["by_scope"]:
        run["notes"].append({"by_scope_s_per_step": out["by_scope"],
                             "flash_tiles": out["flash_tiles"]})
    return run
