"""Flagship benchmark: Llama-decoder LoRA training throughput on one
chip (tokens/sec/chip — the per-chip scale-out unit behind
BASELINE.json's samples/sec/chip metric; the reference publishes no
numbers, see BASELINE.md, so vs_baseline is reported against this
framework's own frozen number in BASELINE.json:"published" once
recorded).

Prints exactly ONE JSON line on stdout and exits nonzero on failure.

Process layout (one process holds the chip at a time, and every
accelerator touch is bounded):

- parent (no jax import): ONE probe subprocess with a hard timeout;
  once it has exited, the measured run in a second subprocess with a
  generous-but-finite timeout, forwarding its JSON line. A host whose
  probe reports the cpu measures the **CPU proxy** instead: a small
  fixed-shape
  llama-LoRA step on ``JAX_PLATFORMS=cpu``, reported as
  ``llama_lora_train_tokens_per_sec_cpu_proxy`` against its own
  committed baseline (BASELINE.json) — the perf trajectory stays
  non-null on every host, and the on-chip metric stays primary when
  hardware exists.
- ``--probe``: initialize the backend, run one tiny op with a host
  readback, print the platform.
- ``--run``: the actual measurement (single jitted lax.scan over
  steps, ended by a host readback).

Warm-start compilation: ``--run`` enables the persistent XLA compile
cache and serves the measured program through
:class:`sparkdl_tpu.parallel.compile.CompiledStepCache`. The cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says; unset, at the fixed
in-checkout ``.jax_cache``
(:func:`sparkdl_tpu.parallel.compile.export_cache_dir`),
so a rerun deserializes the step executable instead of recompiling. The
JSON line carries ``compile_seconds`` (wall time to a ready
executable) and ``warm_start`` (True when it came from the AOT cache),
plus ``steps_per_sec_p50``/``steps_per_sec_p99`` (rate distribution
over repeated invocations of the measured executable; p99 is the slow
tail), ``hbm_high_water_bytes`` (peak device memory from the
``observe.mem`` allocator-stats reader, falling back to live buffer
bytes so the CPU proxy commits a number too),
``host_rss_high_water_bytes`` (host RSS high water — the leak ledger
dimension), and ``step_peak_bytes`` /
``step_peak_bytes_undonated`` / ``step_donated_bytes`` (static peak of
the measured executable from the compiled memory analysis, cpu-safe —
the donation win as a committed number; stats ride the AOT cache entry
so warm starts report them too). ``SPARKDL_TPU_BENCH_NO_DONATE=1``
measures the UNFIXED (undonated) control the CI perf gate compares
against.
"""

import json
import os
import subprocess
import sys
import time

PROBE_TIMEOUT_S = int(os.environ.get("SPARKDL_TPU_BENCH_PROBE_TIMEOUT", 150))
RUN_TIMEOUT_S = int(os.environ.get("SPARKDL_TPU_BENCH_RUN_TIMEOUT", 1500))

CACHE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "benchmarks", "results", "headline_cache.json",
)

METRIC = "llama_lora_train_tokens_per_sec_per_chip"
UNIT = "tokens/sec/chip"

# Deviceless-host headline (ROADMAP item 4, "un-null the perf
# trajectory"): when no accelerator exists the bench measures a SMALL
# FIXED-SHAPE llama-LoRA step on JAX_PLATFORMS=cpu and reports this
# metric against its own committed baseline — every PR lands a real
# number and CPU-visible regressions (dispatch overhead, recompiles,
# input-pipeline stalls) become enforceable. The on-chip METRIC stays
# primary whenever hardware exists. The proxy shape is frozen and
# ignores promoted.json — its trajectory must stay comparable across
# rounds even when the on-chip headline config is re-promoted.
METRIC_CPU = "llama_lora_train_tokens_per_sec_cpu_proxy"
UNIT_CPU = "tokens/sec (cpu proxy)"

# Peak FLOPs for MFU live in ONE place now — the per-device-kind
# table in sparkdl_tpu.observe.perf (SPARKDL_TPU_PEAK_FLOPS still
# overrides) — and the denominator is keyed off the PROBED device
# kind instead of assuming v5e.


def _fail(msg, rc=2, allow_stale=False, attach_cache=False):
    """``allow_stale=True`` is reserved for the PRE-RUN probe failing
    (backend unreachable/wedged before any measured code executed —
    unambiguously an environment failure, not a code failure): emit
    the cached last-good measurement (stale-but-real beats null; the
    driver gate records the parsed value, and ``stale_age_s`` says how
    old it is). Once the measured run has STARTED, no outcome — crash,
    hang, timeout — may fall back with exit 0: a post-hoc probe
    cannot distinguish env from code, and serving yesterday's number
    for today's regression would defeat the gate. Those paths may at
    most ``attach_cache`` the last-good value for context, with
    ``value: null`` and a nonzero exit."""
    if allow_stale:
        cached = _read_cache()
        if cached is not None:
            cached["stale"] = True
            cached["stale_reason"] = msg
            print(json.dumps(cached))
            sys.exit(0)
    rec = {
        "metric": METRIC, "value": None, "unit": UNIT,
        "vs_baseline": None, "error": msg,
    }
    if attach_cache:
        cached = _read_cache()
        if cached is not None:
            rec["cached_last_good"] = {
                k: cached.get(k)
                for k in ("value", "measured_at", "stale_age_s")
            }
    print(json.dumps(rec))
    sys.exit(rc)


# The cache must span a round boundary (a committed mid-round
# measurement serving the end-of-round driver run ~12-24h later), so
# the age gate is wide and ADVISORY within the window: the record
# carries ``stale_age_s`` so the reader can judge freshness instead of
# the bench refusing to serve anything. Beyond the hard cap the value
# is too old to stand in for "current performance" at all.
CACHE_MAX_AGE_S = int(os.environ.get(
    "SPARKDL_TPU_BENCH_CACHE_MAX_AGE", 7 * 24 * 3600))


def _read_cache():
    try:
        with open(CACHE_PATH) as f:
            rec = json.load(f)
        if rec.get("metric") != METRIC or not rec.get("value"):
            return None
        import calendar

        measured = calendar.timegm(time.strptime(
            rec["measured_at"], "%Y-%m-%dT%H:%M:%SZ"))
        age = time.time() - measured
        if age > CACHE_MAX_AGE_S:
            return None
        rec["stale_age_s"] = int(age)
        return rec
    except Exception:
        return None


def _write_cache(payload):
    try:
        os.makedirs(os.path.dirname(CACHE_PATH), exist_ok=True)
        with open(CACHE_PATH, "w") as f:
            json.dump(payload, f)
    except Exception:
        pass


def _baseline_value(metric=METRIC):
    """Frozen own-framework baseline from BASELINE.json (the reference
    publishes no numbers — BASELINE.md)."""
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BASELINE.json")
        with open(path) as f:
            return json.load(f).get("published", {}).get(metric)
    except Exception:
        return None


def _accel_devices_present():
    """True when the host exposes accelerator device nodes.
    Deliberately broad (TPU ``/dev/accel*``, vfio-passthrough TPU
    VMs, CUDA ``/dev/nvidia*``): a host with ANY of these never
    silently downgrades to the CPU proxy on a probe failure."""
    import glob

    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")
                or glob.glob("/dev/nvidia*"))


def _apply_platform_override():
    """SPARKDL_TPU_BENCH_PLATFORM forces a jax platform (CI runs the
    bench machinery on cpu)."""
    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)


def probe():
    """Bounded backend check: init, one op, host readback."""
    _apply_platform_override()
    import jax
    import jax.numpy as jnp
    import numpy as np

    x = jnp.ones((128, 128), jnp.bfloat16)
    np.asarray(x @ x)
    print(jax.devices()[0].platform)


_PROMOTED_KEYS = {"attention": {"reference", "flash"},
                  "loss": {"logits", "fused"},
                  "chunk": None, "ce_bf16": None, "flash_block": None}


def _promoted_config():
    """The winning bench_variants configuration, promoted by data: a
    committed ``benchmarks/promoted.json`` ({"attention": ...,
    "loss": "fused", "chunk": N, "ce_bf16": bool, "flash_block": N})
    redirects the headline measurement without touching code — so a
    sweep's winner lands as a one-file commit. Absent file = the
    long-standing default config. A file that EXISTS but cannot be
    parsed/validated fails the bench loudly: a silently-dropped
    promotion would attribute the default config's number to the
    promoted variant."""
    explicit = os.environ.get("SPARKDL_TPU_BENCH_PROMOTED")
    path = explicit or os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks", "promoted.json",
    )
    try:
        with open(path) as f:
            promoted = json.load(f)
    except FileNotFoundError:
        if explicit:
            raise SystemExit(
                f"bench: SPARKDL_TPU_BENCH_PROMOTED={explicit} does "
                "not exist")
        return {}
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"bench: unreadable promoted config {path}: {e}")
    for key, allowed in sorted(_PROMOTED_KEYS.items()):
        if key in promoted and allowed is not None \
                and promoted[key] not in allowed:
            raise SystemExit(
                f"bench: promoted.json {key}={promoted[key]!r} not in "
                f"{sorted(allowed)}")
    unknown = set(promoted) - set(_PROMOTED_KEYS)
    if unknown:
        raise SystemExit(
            f"bench: unknown promoted.json keys {sorted(unknown)}")
    return promoted


def run():
    # before jax is imported: JAX reads its cache variable once
    from sparkdl_tpu.parallel.compile import export_cache_dir

    export_cache_dir()
    _apply_platform_override()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from sparkdl_tpu.models import Llama, LlamaConfig, lora_mask
    from sparkdl_tpu.parallel.compile import (
        CompiledStepCache,
        enable_persistent_cache,
    )
    from sparkdl_tpu.parallel.train import (
        make_lm_loss_fn,
        make_train_step,
        param_count,
    )

    # Persistent XLA cache for every jit in this process (init paths
    # included) + the AOT executable cache for the measured program
    # below: a rerun deserializes and goes.
    cache_dir = enable_persistent_cache()

    cpu_proxy = bool(os.environ.get("SPARKDL_TPU_BENCH_CPU_PROXY"))
    promoted = {} if cpu_proxy else _promoted_config()
    # flash_block rides LlamaConfig (part of the jit cache key), not
    # the env var (read once at attention-module import).
    flash_block = int(promoted.get("flash_block", 0))
    attention = promoted.get("attention", "reference")
    n_steps = 20
    if cpu_proxy:
        # Deviceless-host headline: a FIXED small shape, big enough
        # that the scanned step dominates dispatch, small enough that
        # the whole measurement (warm + timed + p50/p99 reps) stays
        # under a minute on one CPU. Frozen independently of
        # promoted.json — see METRIC_CPU.
        cfg = LlamaConfig(
            vocab_size=4096, d_model=256, n_layers=4, n_heads=8,
            n_kv_heads=4, d_ff=1024, dtype=jnp.bfloat16, lora_rank=8,
        )
        batch, seq = 4, 256
        n_steps = 8
    elif os.environ.get("SPARKDL_TPU_BENCH_TINY"):
        # CI smoke config: exercises the full measurement path in
        # seconds on cpu; numbers are not meaningful.
        cfg = LlamaConfig(
            vocab_size=512, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=256, dtype=jnp.bfloat16, lora_rank=4,
            attention=attention, flash_block=flash_block,
        )
        batch, seq = 2, 128
    else:
        cfg = LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=4096, dtype=jnp.bfloat16, lora_rank=16,
            attention=attention, flash_block=flash_block,
        )
        batch, seq = 8, 1024
    model = Llama(cfg)
    tokens = np.zeros((batch, seq), np.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    mask = lora_mask(params)
    # optax.masked: the optimizer carries moments ONLY for the LoRA
    # adapters — the full-tree alternative reads+writes ~2x params of
    # frozen adam state from HBM every step for nothing.
    opt = optax.masked(optax.adamw(1e-4), mask)
    opt_state = opt.init(params)

    # Shared builder with bench_variants: the config the sweep measured
    # is byte-for-byte the config a promotion runs. The loss-chunk knob
    # is env-tunable (SPARKDL_TPU_LOSS_CHUNK — the perf.autotune
    # microbatching axis); a committed promoted.json still wins, since
    # a promotion is a measured decision for THIS host class.
    from sparkdl_tpu.utils.knobs import read_int

    loss_fn = make_lm_loss_fn(
        model, loss=promoted.get("loss", "logits"),
        chunk=int(promoted["chunk"]) if "chunk" in promoted
        else read_int("SPARKDL_TPU_LOSS_CHUNK", 512),
        ce_bf16=bool(promoted.get("ce_bf16")),
    )

    step = make_train_step(loss_fn, opt, param_mask=mask)
    rng = np.random.default_rng(0)
    batch_data = {
        "inputs": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                              jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                               jnp.int32),
    }

    # The whole measured loop lives inside ONE jitted program
    # (lax.scan over steps), so per-step dispatch is not in the
    # measurement. (Same pattern as MaxText-style benchmarking.)
    def run_n(params, opt_state, b):
        def body(carry, _):
            p, s = carry
            p, s, m = step(p, s, b)
            return (p, s), m["loss"]

        (p, s), losses = jax.lax.scan(
            body, (params, opt_state), None, length=n_steps
        )
        return p, s, losses[-1]

    # One lowering serves the AOT cache lookup and (on a miss) the
    # cold compile — the donate_argnums ride the Lowered, so the
    # deserialized and cold paths donate identically. The carried
    # state IS donated by default (the lint-to-fix donation contract:
    # zero `undonated-step-buffers` findings on the repo's own step
    # paths); SPARKDL_TPU_BENCH_NO_DONATE=1 is the UNFIXED control the
    # CI perf gate measures against — the fix must never be slower.
    donate = () if os.environ.get(
        "SPARKDL_TPU_BENCH_NO_DONATE", "").strip() in ("1", "true", "yes") \
        else (0, 1)
    lowered = jax.jit(run_n, donate_argnums=donate).lower(
        params, opt_state, batch_data)
    t_compile0 = time.perf_counter()
    if cache_dir:
        step_cache = CompiledStepCache(cache_dir)
        run_n = step_cache.load_or_compile(lowered, name="bench_run_n")
        warm_start = step_cache.hits > 0
        step_mem = step_cache.last_memory_stats
    else:
        # no safe cache dir: plain cold compile, still timed
        run_n = lowered.compile()
        warm_start = False
        from sparkdl_tpu.utils.jax_compat import memory_analysis

        step_mem = memory_analysis(run_n)
    compile_seconds = time.perf_counter() - t_compile0
    sys.stderr.write(
        "bench: step executable ready in %.2fs (%s)\n"
        % (compile_seconds, "warm start" if warm_start else "cold compile")
    )

    # warm run (buffers are donated: thread them through)
    params, opt_state, last = run_n(params, opt_state, batch_data)
    _ = np.asarray(last)

    # --capture: wrap the measured region (timed run + rate reps,
    # warm-up excluded) in the same bounded-profile shim the live
    # forensics capture uses (jax_compat.profiler_trace — None-never-
    # raise, so a runtime without the profiler still measures); the
    # artifact path rides the JSON line as ``capture_dir``.
    capture_trace = capture_dir = None
    if os.environ.get("SPARKDL_TPU_BENCH_CAPTURE") \
            or "--capture" in sys.argv:
        from sparkdl_tpu.utils import jax_compat

        target = os.environ.get("SPARKDL_TPU_BENCH_CAPTURE_DIR") \
            or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "benchmarks", "results", "xprof-bench")
        capture_trace = jax_compat.profiler_trace(target)
        capture_dir = capture_trace.__enter__()

    t0 = time.perf_counter()
    params, opt_state, last = run_n(params, opt_state, batch_data)
    last_loss = float(np.asarray(last))  # host readback = true sync
    dt = time.perf_counter() - t0
    assert np.isfinite(last_loss)

    tokens_per_sec = n_steps * batch * seq / dt

    # Steps/sec distribution + HBM high-water (ISSUE: observability).
    # A few more timed invocations of the SAME measured executable
    # give a steps/sec sample set (p50/p99 expose jitter a single
    # headline number hides — a noisy neighbor, a thermal throttle);
    # the memory gauge comes from observe.health.export_device_memory,
    # the exact helper each gang worker's heartbeat exports
    # device_hbm_bytes{kind=} from, so the bench's high-water and a
    # live gang's agree by construction. Null on deviceless hosts —
    # a CPU rig has no HBM to report.
    rates = [n_steps / dt]
    for _ in range(3):
        t0 = time.perf_counter()
        params, opt_state, last = run_n(params, opt_state, batch_data)
        _ = float(np.asarray(last))
        rates.append(n_steps / (time.perf_counter() - t0))
    if capture_trace is not None:
        capture_trace.__exit__(None, None, None)
    # p99 is the SLOW tail (the rate at the 99th percentile of step
    # latency — reciprocal is monotonic, so that's the 1st percentile
    # of the rate samples): p99 <= p50 by construction.
    steps_per_sec_p50 = float(np.percentile(rates, 50))
    steps_per_sec_p99 = float(np.percentile(rates, 1))

    # Dynamic memory high waters (observe.mem): device peak from the
    # allocator stats where the backend reports them (falls back to
    # live buffer bytes, so the CPU proxy commits a number too instead
    # of null) and host RSS high water from /proc / getrusage — the
    # host-side leak ledger the rss-growth alert judges against.
    from sparkdl_tpu.observe import mem as mem_acct

    hbm_high_water = mem_acct.device_peak_bytes()
    host_rss_high_water = mem_acct.host_rss_high_water_bytes()

    # Static peak of the measured step executable (compiled memory
    # analysis; cpu-safe, unlike the device HBM gauge above). The
    # donation win is a committed number, not an assertion: the
    # undonated figure is the same module WITHOUT the alias credit —
    # what peak would be had the carried state not been donated
    # (ROADMAP item 3 / the lint-to-fix donation contract; the fix
    # engine's budget-delta proof reads the identical quantities).
    step_peak_bytes = step_peak_undonated = step_donated = None
    if step_mem:
        # peak_bytes is THE one spelling of the formula (shared with
        # the fix engine's budget proof), including the fallback for
        # executables served from the XLA persistent compile cache,
        # which deserialize without alias accounting — the donation
        # attrs on the lowering are the exact figure.
        from sparkdl_tpu.analysis.fixes import peak_bytes
        from sparkdl_tpu.utils.jax_compat import lowered_stablehlo

        step_peak_bytes = int(
            peak_bytes(step_mem, lowered_stablehlo(lowered)))
        step_peak_undonated = int(
            step_mem.get("argument_size_in_bytes", 0)
            + step_mem.get("output_size_in_bytes", 0)
            + step_mem.get("temp_size_in_bytes", 0))
        step_donated = step_peak_undonated - step_peak_bytes

    # Model FLOPs/token (matmul terms only, causal attention halved):
    #   forward        2N        (N = non-embedding matmul params)
    #   backward dX    2N        (chain rule through frozen weights)
    #   backward dW    2N_train  (only LoRA adapters accumulate grads)
    #   attention      fwd 4*S*d_model (QK^T and AV each 2*S*d),
    #                  x3 for fwd+bwd, causal /2
    n_total = param_count(params)
    n_embed = cfg.vocab_size * cfg.d_model
    n_matmul = n_total - n_embed  # lm_head counts; the lookup doesn't
    n_train = sum(
        int(np.prod(p.shape))
        for p, m in zip(jax.tree.leaves(params), jax.tree.leaves(mask))
        if m
    )
    attn = 3 * (4 * seq * cfg.d_model) / 2 * cfg.n_layers
    flops_per_token = 4 * n_matmul + 2 * n_train + attn
    model_flops_per_sec = flops_per_token * tokens_per_sec

    from sparkdl_tpu.observe import perf

    device_kind = perf.device_kind()
    mfu = model_flops_per_sec / perf.peak_flops(device_kind)

    base = _baseline_value(METRIC_CPU if cpu_proxy else METRIC)
    rec = {
        "metric": METRIC_CPU if cpu_proxy else METRIC,
        "value": round(tokens_per_sec, 1),
        "unit": UNIT_CPU if cpu_proxy else UNIT,
        "vs_baseline": (round(tokens_per_sec / base, 3)
                        if base else 1.0),
        "platform": jax.devices()[0].platform,
        "last_loss": round(last_loss, 4),
        "compile_seconds": round(compile_seconds, 3),
        "warm_start": warm_start,
        "steps_per_sec_p50": round(steps_per_sec_p50, 3),
        "steps_per_sec_p99": round(steps_per_sec_p99, 3),
        "hbm_high_water_bytes": hbm_high_water,
        "host_rss_high_water_bytes": host_rss_high_water,
        "step_peak_bytes": step_peak_bytes,
        "step_peak_bytes_undonated": step_peak_undonated,
        "step_donated_bytes": step_donated,
        "device_kind": device_kind,
        # who measured this: observe.compare treats records from a
        # different host fingerprint as advisory, not enforceable
        "host": perf.host_fingerprint(),
        "rate_samples": [round(r * batch * seq, 1) for r in rates],
        **({"promoted": promoted} if promoted else {}),
        **({"capture_dir": capture_dir}
           if capture_trace is not None else {}),
    }
    if not cpu_proxy:
        # MFU is computed against the CHIP's peak FLOPs — meaningless
        # for the CPU proxy, whose contract is trajectory, not
        # utilization.
        rec["mfu"] = round(mfu, 4)
        rec["model_tflops_per_sec"] = round(model_flops_per_sec / 1e12, 1)
    # Regression ledger (observe.perf): one schema-versioned line per
    # measured run in benchmarks/results/history.jsonl — the file
    # `python -m sparkdl_tpu.observe.compare` diffs and the CI perf
    # gate enforces. Best-effort: the ledger never fails the bench.
    perf.append_history(perf.history_record(
        {rec["metric"]: {
            "value": rec["value"], "unit": rec["unit"],
            "samples": rec["rate_samples"],
            # p50/p99 in the metric's own unit (tokens/sec), not the
            # steps/sec the JSON record reports alongside
            "p50": round(steps_per_sec_p50 * batch * seq, 1),
            "p99": round(steps_per_sec_p99 * batch * seq, 1),
        }},
        device_kind=device_kind, bench="bench.py",
        extra={"warm_start": warm_start,
               "compile_seconds": rec["compile_seconds"],
               "hbm_high_water_bytes": hbm_high_water,
               "host_rss_high_water_bytes": host_rss_high_water},
    ))
    print(json.dumps(rec))


def _bounded_run(args, env, timeout):
    """subprocess with a REAL timeout: a child wedged in the TPU
    runtime can survive SIGKILL-then-communicate() (subprocess.run's
    TimeoutExpired path blocks on the pipes forever) — so kill the
    whole process group and abandon the pipes after a grace period.
    Returns (rc_or_None, stdout, stderr)."""
    import signal

    p = subprocess.Popen(
        args, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True,
    )
    try:
        out, err = p.communicate(timeout=timeout)
        return p.returncode, out, err
    except subprocess.TimeoutExpired:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            p.kill()
        try:
            out, err = p.communicate(timeout=10)
        except subprocess.TimeoutExpired:
            out, err = "", ""
        return None, out, err


def orchestrate():
    from sparkdl_tpu.parallel.compile import export_cache_dir

    env = dict(os.environ)
    export_cache_dir(env)   # the probe and the run share one cache
    here = os.path.abspath(__file__)
    if "--capture" in sys.argv:
        # the measured run lands in a child subprocess whose argv we
        # own — forward the flag through the env it does inherit
        env["SPARKDL_TPU_BENCH_CAPTURE"] = "1"

    def attempt_probe():
        rc, out, err = _bounded_run(
            [sys.executable, here, "--probe"], env, PROBE_TIMEOUT_S
        )
        if rc is None:
            return None, f"probe timeout after {PROBE_TIMEOUT_S}s"
        if rc != 0:
            return None, "probe rc=%d: %s" % (rc, err.strip()[-400:])
        return out.strip().splitlines()[-1], None

    # One probe: each run gets a machine of its own, so a probe that
    # fails once fails again.
    have_accel = _accel_devices_present()
    platform, err = attempt_probe()
    if platform is None:
        if not have_accel and not env.get("SPARKDL_TPU_BENCH_PLATFORM"):
            # Probe died without device nodes and without an explicit
            # platform pin: force cpu for the measured child — the CPU
            # proxy is the deviceless contract either way.
            sys.stderr.write(
                f"bench: probe failed ({err}) with no /dev/accel* — "
                "forcing the cpu backend for the proxy measurement\n")
            env["SPARKDL_TPU_BENCH_PLATFORM"] = "cpu"
            platform = "cpu"
        else:
            _fail(f"accelerator backend unavailable: {err}",
                  allow_stale=True)

    if platform == "cpu" and not env.get("SPARKDL_TPU_BENCH_TINY"):
        # Deviceless host: measure the small fixed-shape CPU proxy
        # instead of dragging the full on-chip config through a CPU
        # (hours) or emitting null. TINY keeps its own path — CI uses
        # it to exercise the on-chip measurement machinery on cpu.
        env["SPARKDL_TPU_BENCH_CPU_PROXY"] = "1"
        sys.stderr.write(
            "bench: cpu backend — measuring the fixed-shape CPU-proxy "
            f"headline ({METRIC_CPU})\n")

    sys.stderr.write(f"bench: backend healthy ({platform}); running\n")
    rc, out, err = _bounded_run(
        [sys.executable, here, "--run"], env, RUN_TIMEOUT_S
    )
    if rc is None:
        # A run timeout can NOT be disambiguated after the fact: a
        # deadlocked collective (code bug) hangs exactly like an
        # environment failure, so a re-probe failing proves
        # nothing. Never serve the cache with exit 0 here — attach the
        # last-good value for context only, value stays null.
        _fail(f"measured run timeout after {RUN_TIMEOUT_S}s", rc=3,
              attach_cache=True)
    sys.stderr.write(err[-2000:])
    if rc != 0:
        _fail("measured run rc=%d: %s" % (rc, err.strip()[-400:]), rc=3)
    # forward exactly the run's single JSON line; cache a real
    # accelerator measurement for the stale-fallback path
    line = out.strip().splitlines()[-1]
    try:
        payload = json.loads(line)
        if payload.get("value") and payload.get("platform") not in (
                None, "cpu"):
            payload["measured_at"] = time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
            _write_cache(payload)
    except Exception:
        pass
    print(line)


if __name__ == "__main__":
    import warnings

    warnings.filterwarnings("ignore")
    if "--probe" in sys.argv:
        probe()
    elif "--run" in sys.argv:
        sys.stderr.write("bench: llama-lora single-chip train throughput\n")
        run()
    else:
        orchestrate()
