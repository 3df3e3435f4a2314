"""The knob registry: every ``SPARKDL_TPU_*`` environment variable,
registered once — knobs are data, not code.

The platform has grown ~90 env-var knobs across nine subsystems, each
documented (at best) in the module that reads it. This registry is the
single catalog: name, type, default, owning subsystem, one-liner, and
— the reason it exists — whether the knob is **tunable**: a
performance setting the :mod:`sparkdl_tpu.perf.autotune` search driver
may legitimately vary per machine, as opposed to wiring (ranks,
addresses, secrets), test rig plumbing, or chaos injection. The
autotuner derives its search space from :func:`tunable_knobs`; nothing
else in the repo may hand-roll a knob list (the same "Param surface is
data" idiom as ``sparkdl/xgboost``'s booster params, reference
``xgboost.py:304-305``).

Drift protection (same pattern as the analysis ``--list-rules`` docs
gate): ``tests/utils/test_knobs.py`` greps the source tree for
``SPARKDL_TPU_`` reads and fails on any name missing here, so a new
env var cannot land unregistered — and every TUNABLE knob must appear
in ``docs/performance.rst``'s knob catalog.

Dynamic families (e.g. the chaos hooks, which compose names like
``SPARKDL_TPU_CHAOS_KILL_RANK`` at injection sites) are registered as
explicit members plus a :data:`PREFIX_FAMILIES` prefix so composed
spellings in helper code never false-positive the drift gate.

Tunable knobs carry two extra fields the search driver consumes:

- ``trial_values``: the candidate values a short autotune trial may
  measure (the declared space — small on purpose; an operator widens
  it per-run with ``--values``).
- ``component``: the step-time attribution component (or serving
  stat) that must be *material* for the knob to matter. The pruner
  drops the knob when a measured report shows that component is
  negligible — a step that is 80% compute never explores prefetch
  depth; a serving run with near-zero queue wait never explores
  ``max_queue``. ``None`` = never pruned.
"""

import dataclasses
import os

__all__ = [
    "Knob",
    "KNOBS",
    "PREFIX_FAMILIES",
    "all_knobs",
    "get",
    "is_registered",
    "registered_names",
    "tunable_knobs",
    "read",
]


@dataclasses.dataclass(frozen=True)
class Knob:
    """One registered env var. ``default`` is the documented effective
    default (as the reading site interprets an unset var), kept as a
    string or None — informational, the reading site stays the source
    of truth at runtime."""

    name: str
    type: str            # int | float | bool | str | enum | path | list
    default: str = None
    subsystem: str = "misc"
    help: str = ""
    tunable: bool = False
    trial_values: tuple = ()
    benches: tuple = ()  # trial harnesses that honor it:
                         # serve|gbdt|attention
    component: str = None  # attribution component gating its relevance


# Name prefixes that generate member names dynamically (the chaos
# injection helpers build "SPARKDL_TPU_CHAOS_" + hook spellings).
PREFIX_FAMILIES = ("SPARKDL_TPU_CHAOS_",)


def _build():
    def k(name, type_, default=None, subsystem="misc", help_="",
          tunable=False, trial_values=(), benches=(), component=None):
        return Knob(name=name, type=type_, default=default,
                    subsystem=subsystem, help=help_, tunable=tunable,
                    trial_values=tuple(str(v) for v in trial_values),
                    benches=tuple(benches), component=component)

    knobs = [
        # -- tunable performance knobs (the autotune search space) ---
        k("SPARKDL_TPU_PREFETCH_DEPTH", "int", "2", "data",
          "host-side producer queue bound of prefetch_to_device "
          "(deeper read-ahead for spiky producers)",
          tunable=True, trial_values=(2, 4, 8),
          component="data_wait"),
        k("SPARKDL_TPU_OVERLAP", "bool", "1", "parallel",
          "default overlap schedule for ring attention / pipeline "
          "hops when the caller does not pass overlap= explicitly",
          tunable=True, trial_values=("0", "1"), component="collective"),
        k("SPARKDL_TPU_SPEC_DRAFT_K", "int", "4", "serving",
          "speculative-decode draft length (tokens proposed per "
          "verify round) when the caller does not pass k=",
          tunable=True, trial_values=(2, 4, 8)),
        k("SPARKDL_TPU_KV_PAGE_SIZE", "int", "0", "serving",
          "serve_bench default --page-size: 0 = dense slot cache, "
          ">0 = paged KV pool", tunable=True, trial_values=(0, 32),
          benches=("serve",)),
        k("SPARKDL_TPU_SERVE_DECODE_CHUNK", "int", None, "serving",
          "serve_bench decode chunk (engine steps per scheduler "
          "turn); default = bench shape default",
          tunable=True, trial_values=(4, 8, 16), benches=("serve",)),
        k("SPARKDL_TPU_SERVE_REPLICAS", "int", "1", "serving",
          "serve_bench default --replicas (FleetFrontend fan-out)",
          tunable=True, trial_values=(1, 2), benches=("serve",)),
        k("SPARKDL_TPU_SERVE_MAX_QUEUE", "int", None, "serving",
          "serve_bench default --max-queue (fleet admission bound; "
          "default 4x total slots)", tunable=True,
          trial_values=(16, 64), benches=("serve",),
          component="queue_wait"),
        k("SPARKDL_TPU_SERVE_QUANT", "enum", "", "serving",
          "serve_bench default --quant ('' | int8 | int4 weight-only "
          "serving)", tunable=True, trial_values=("", "int8"),
          benches=("serve",)),
        k("SPARKDL_TPU_GBDT_MAX_BINS", "int", "256", "gbdt",
          "gbdt_bench histogram bin count (the XGBoost-hist bins-are-"
          "data knob)", tunable=True, trial_values=(64, 128, 256),
          benches=("gbdt",)),

        # -- perf platform ------------------------------------------
        k("SPARKDL_TPU_PERF_PROFILE", "path", None, "perf",
          "autotuned profile the launcher pre-flight applies: a "
          "profile JSON, a directory of per-device-kind profiles "
          "(default benchmarks/profiles/), or 0/off to disable"),
        k("SPARKDL_TPU_PERF_HISTORY", "path", None, "perf",
          "history.jsonl ledger path override (0/off disables)"),
        k("SPARKDL_TPU_PEAK_FLOPS", "float", None, "perf",
          "peak FLOPs/s override for MFU denominators"),
        k("SPARKDL_TPU_PEAK_BYTES_PER_S", "float", None, "perf",
          "peak HBM bytes/s override"),
        k("SPARKDL_TPU_PEAK_ICI_BYTES_PER_S", "float", None, "perf",
          "aggregate per-chip ICI bytes/s override"),
        k("SPARKDL_TPU_HBM_BYTES", "float", None, "perf",
          "per-chip HBM capacity override (enables overcommit checks "
          "on cpu)"),

        # -- bench orchestration ------------------------------------
        k("SPARKDL_TPU_BENCH_TINY", "bool", "0", "bench",
          "CI smoke shape: exercise the measurement path in seconds; "
          "numbers are not meaningful"),
        k("SPARKDL_TPU_BENCH_PLATFORM", "str", None, "bench",
          "force a jax platform for bench children"),
        k("SPARKDL_TPU_SERVE_SMOKE_TTFT_P99_S", "float", None, "bench",
          "serve smoke p99 TTFT bound override"),
        k("SPARKDL_TPU_SERVE_SMOKE_INTER_TOKEN_P99_S", "float", None,
          "bench", "serve smoke p99 inter-token bound override"),
        k("SPARKDL_TPU_COLOCATION_TTFT_P99_S", "float", None, "bench",
          "colocation smoke client p99 TTFT bound override"),

        # -- gang wiring (launcher/worker contract) -----------------
        k("SPARKDL_TPU_RANK", "int", None, "gang", "worker rank"),
        k("SPARKDL_TPU_SIZE", "int", None, "gang", "gang size"),
        k("SPARKDL_TPU_LOCAL_RANK", "int", None, "gang",
          "rank within this host"),
        k("SPARKDL_TPU_LOCAL_SIZE", "int", None, "gang",
          "ranks on this host"),
        k("SPARKDL_TPU_COORDINATOR", "str", None, "gang",
          "jax.distributed rendezvous address"),
        k("SPARKDL_TPU_COORDINATOR_PORT", "int", None, "gang",
          "pinned coordinator port for remote rank-0 hosts"),
        k("SPARKDL_TPU_CONTROL_ADDR", "str", None, "gang",
          "driver control-plane address"),
        k("SPARKDL_TPU_CONTROL_SECRET", "str", None, "gang",
          "per-job control-plane credential"),
        k("SPARKDL_TPU_PAYLOAD", "path", None, "gang",
          "cloudpickled (main, kwargs) path; '-' = stdin"),
        k("SPARKDL_TPU_JOB_DIR", "path", None, "gang",
          "per-attempt job dir (logs, payloads)"),
        k("SPARKDL_TPU_HOSTS", "str", None, "gang",
          "hosts x slots topology spec"),
        k("SPARKDL_TPU_NUM_SLOTS", "int", None, "gang",
          "task-slot count override (what the slots are is still "
          "probed)"),
        k("SPARKDL_TPU_SLOT_DIR", "path", None, "gang",
          "slot claim-file registry dir"),
        k("SPARKDL_TPU_SLOT_WAIT_TIMEOUT", "float", "600", "gang",
          "wait for busy slots before giving up (s)"),
        k("SPARKDL_TPU_START_TIMEOUT", "float", "300", "gang",
          "gang rendezvous deadline (s)"),
        k("SPARKDL_TPU_ABORT_GRACE", "float", "30", "gang",
          "grace before killing survivors of a dead rank (s)"),
        k("SPARKDL_TPU_DUMP_GRACE", "float", "10", "gang",
          "wait for stalled ranks' stack dumps before the kill (s)"),
        k("SPARKDL_TPU_WORKER_PLATFORM", "str", None, "gang",
          "jax platform for workers"),
        k("SPARKDL_TPU_FORCE_PLATFORM", "str", None, "gang",
          "worker-side platform pin shipped by the launcher"),
        k("SPARKDL_TPU_REMOTE_SHELL", "str", None, "gang",
          "remote-exec command override (none disables)"),
        k("SPARKDL_TPU_REMOTE_PYTHON", "path", None, "gang",
          "python on task nodes"),
        k("SPARKDL_TPU_MAX_RESULT_BYTES", "int", None, "gang",
          "cap on rank 0's cloudpickled result"),
        k("SPARKDL_TPU_VAL_GATHER_WARN_BYTES", "int", None, "gang",
          "validation-gather size warning threshold"),
        k("SPARKDL_TPU_XGB_STRICT_SLOTS", "bool", "0", "gbdt",
          "fail (not shrink) when num_workers exceeds slots"),

        # -- supervision / elasticity -------------------------------
        k("SPARKDL_TPU_GANG_MAX_RETRIES", "int", "0", "supervisor",
          "relaunch budget for transient failures"),
        k("SPARKDL_TPU_MAX_RESTARTS", "int", "0", "supervisor",
          "legacy alias of GANG_MAX_RETRIES (transient-only)"),
        k("SPARKDL_TPU_GANG_BACKOFF_BASE", "float", "1.0",
          "supervisor", "backoff base (s)"),
        k("SPARKDL_TPU_GANG_BACKOFF_FACTOR", "float", "2.0",
          "supervisor", "backoff growth factor"),
        k("SPARKDL_TPU_GANG_BACKOFF_MAX", "float", "60.0",
          "supervisor", "backoff cap (s)"),
        k("SPARKDL_TPU_GANG_BACKOFF_JITTER", "float", "0.5",
          "supervisor", "jitter fraction on top of each delay"),
        k("SPARKDL_TPU_GANG_RESUME_DIR", "path", None, "supervisor",
          "TrainCheckpointer root for resume-step discovery"),
        k("SPARKDL_TPU_GANG_RELAUNCH_NP", "int", None, "supervisor",
          "elastic relaunch target np (reshard pre-flight gated)"),
        k("SPARKDL_TPU_TRANSIENT_PATTERNS", "list", None,
          "supervisor", "extra transient traceback signatures"),
        k("SPARKDL_TPU_RESTART_ATTEMPT", "int", None, "supervisor",
          "restart context: attempt number (worker-read)"),
        k("SPARKDL_TPU_RESUME_STEP", "int", None, "supervisor",
          "restart context: latest committed checkpoint step"),
        k("SPARKDL_TPU_RESHARD_SOURCE_AXES", "str", None, "supervisor",
          "restart context: JSON mesh axes the resume checkpoint was "
          "laid out on (worker-read)"),
        k("SPARKDL_TPU_RESHARD_TARGET_AXES", "str", None, "supervisor",
          "restart context: JSON mesh axes shrink_mesh derived for "
          "the elastic relaunch target np (worker-read)"),
        k("SPARKDL_TPU_RESHARD_GROUPED", "int", "0", "supervisor",
          "resharded-restore group size override: >0 places that many "
          "params per group; 0 = auto (group only when the restore "
          "high-water approaches the HBM budget)"),

        # -- autonomous elasticity (ISSUE 16) -----------------------
        k("SPARKDL_TPU_ELASTIC", "bool", "0", "supervisor",
          "enable the capacity-watching elastic controller: grow the "
          "gang back autonomously when chips return (unset = no "
          "object, no probe, no thread)"),
        k("SPARKDL_TPU_ELASTIC_PROBE", "enum", "auto", "supervisor",
          "capacity probe: auto | env | file | devices (/dev/accel* "
          "count) | slots (local slot table)"),
        k("SPARKDL_TPU_ELASTIC_CAPACITY", "int", None, "supervisor",
          "capacity override in chips (tests/chaos; wins in auto "
          "probe order)"),
        k("SPARKDL_TPU_ELASTIC_CAPACITY_FILE", "path", None,
          "supervisor", "file re-read every poll whose content is the "
          "chip capacity (chaos harnesses flip it mid-run)"),
        k("SPARKDL_TPU_ELASTIC_CHECK_S", "float", "2.0", "supervisor",
          "capacity poll cadence (s)"),
        k("SPARKDL_TPU_ELASTIC_DEBOUNCE_S", "float", "10",
          "supervisor", "surplus capacity must hold this long before "
          "a grow is planned (flap guard — never thrash shrink/grow)",
          tunable=True, trial_values=(5, 10, 30)),
        k("SPARKDL_TPU_ELASTIC_MARGIN", "float", "0.8", "supervisor",
          "ledger gate: a measured candidate np must retain at least "
          "this fraction of the current per-chip throughput or the "
          "grow is refused as unprofitable",
          tunable=True, trial_values=(0.7, 0.8, 0.9)),
        k("SPARKDL_TPU_ELASTIC_CKPT_WAIT_S", "float", "60",
          "supervisor", "max wait for a step boundary (committed "
          "checkpoint) after a resize decision before falling back "
          "to the newest committed step (none at all = cancel)"),
        k("SPARKDL_TPU_ELASTIC_MAX_NP", "int", None, "supervisor",
          "hard cap on the elastic grow target"),
        k("SPARKDL_TPU_ELASTIC_MIN_NP", "int", "1", "supervisor",
          "floor the arbiter may not shrink training below"),
        k("SPARKDL_TPU_ELASTIC_ARBITER", "bool", "0", "supervisor",
          "enable the train/serve chip-budget arbiter: serving "
          "alerts demand chips, training yields and reclaims"),
        k("SPARKDL_TPU_ELASTIC_ARBITER_RULES", "list",
          "queue_depth_growth,server_ttft", "supervisor",
          "alert rules whose firings count as serving chip demand"),
        k("SPARKDL_TPU_ELASTIC_ARBITER_CHIPS", "int", "1",
          "supervisor", "chips yielded per arbiter demand"),
        k("SPARKDL_TPU_ELASTIC_ARBITER_CLEAR_S", "float", "30",
          "supervisor", "quiet period (no demand, drained fleet "
          "queue) before training reclaims yielded chips"),

        # -- static analysis pre-flight -----------------------------
        k("SPARKDL_TPU_PREFLIGHT_LINT", "bool", "0", "analysis",
          "launcher pre-flight: lint payload + registered steps, "
          "refuse launch on ERROR findings"),
        k("SPARKDL_TPU_PREFLIGHT_FIX", "bool", "0", "analysis",
          "launcher pre-flight: run the verified fix engine over "
          "registered callable steps"),

        # -- concurrency sanitizer (utils.locksan) ------------------
        k("SPARKDL_TPU_CONCUR_SAN", "bool", "0", "analysis",
          "instrument threading.Lock/RLock at boot: record per-"
          "thread acquisition stacks, build the observed lock-order "
          "graph, report inversions/cycles and long holds "
          "(concur_report.json + concur.* timeline instants)"),
        k("SPARKDL_TPU_CONCUR_HOLD_WARN_S", "float", "1.0", "analysis",
          "sanitizer long-hold threshold: a lock held at least this "
          "many seconds lands in the report"),
        k("SPARKDL_TPU_CONCUR_REPORT", "path", None, "analysis",
          "sanitizer report destination; default "
          "$SPARKDL_TPU_TELEMETRY_DIR/concur_report.json when "
          "telemetry is on, else no file"),
        k("SPARKDL_TPU_CONCUR_STACK_DEPTH", "int", "12", "analysis",
          "frames kept per recorded acquisition stack"),

        # -- observability ------------------------------------------
        k("SPARKDL_TPU_TELEMETRY_DIR", "path", None, "observe",
          "opt-in telemetry root (run-* dirs)"),
        k("SPARKDL_TPU_TELEMETRY_FLUSH_S", "float", None, "observe",
          "periodic driver-side artifact flush interval"),
        k("SPARKDL_TPU_HEARTBEAT_S", "float", None, "observe",
          "worker heartbeat period"),
        k("SPARKDL_TPU_STALL_S", "float", None, "observe",
          "per-rank stall threshold for the hang detector"),
        k("SPARKDL_TPU_SERVE_HANG_S", "float", None, "observe",
          "serving doctor hang threshold"),
        k("SPARKDL_TPU_SERVING_WRITE_S", "float", None, "observe",
          "serving telemetry write period"),
        k("SPARKDL_TPU_SERVING_TRACE_EVENTS", "int", None, "observe",
          "serving span-tree event cap"),
        k("SPARKDL_TPU_FLIGHTREC_EVENTS", "int", None, "observe",
          "flight-recorder ring capacity"),
        k("SPARKDL_TPU_PROFILE", "str", None, "observe",
          "utils.profiler opt-in (jax profiler traces)"),

        # -- perf forensics (ISSUE 20) ------------------------------
        k("SPARKDL_TPU_PROFILE_ON_ALERT", "bool", "0", "observe",
          "perf-alert firings trigger an on-demand forensic capture "
          "on the offending rank (xprof trace + uncapped attribution "
          "window + regression_report.json diff)"),
        k("SPARKDL_TPU_PROFILE_STEPS", "int", "20", "observe",
          "train steps one forensic capture window spans (wall-clock "
          "capped so a wedged step releases the profiler)"),
        k("SPARKDL_TPU_PROFILE_COOLDOWN_S", "float", "300", "observe",
          "per-(rule, rank) cooldown between alert-triggered "
          "captures (flap guard; manual /capturez is exempt)"),
        k("SPARKDL_TPU_PROFILE_AT_STEP", "int", None, "observe",
          "worker-side fixed-step A/B trigger: capture one forensic "
          "window when the rank reaches this train step"),
        k("SPARKDL_TPU_BENCH_CAPTURE_DIR", "path", None, "observe",
          "where serve_bench --capture writes its xprof trace "
          "(defaults beside the bench JSON)"),
        k("SPARKDL_TPU_NATIVE_LOGS", "bool", None, "observe",
          "native control-plane log transport toggle"),

        # -- memory accounting (ISSUE 18) ---------------------------
        k("SPARKDL_TPU_MEM_SAMPLE_S", "float", "2.0", "observe",
          "memory sampler cadence (s): HBM stats + host RSS + "
          "per-category gauges"),
        k("SPARKDL_TPU_MEM_TOP_BUFFERS", "int", "8", "observe",
          "rows kept in the (shape, dtype)-aggregated largest-live-"
          "buffer table of samples and OOM reports"),
        k("SPARKDL_TPU_MEM_SAMPLES", "int", "64", "observe",
          "in-process rolling memory sample tail length (feeds OOM "
          "reports and beacons)"),

        # -- live status & alerts (ISSUE 14) ------------------------
        k("SPARKDL_TPU_STATUSZ_PORT", "int", None, "observe",
          "driver-side live status HTTP port (GET /metrics, "
          "/statusz, /events); unset = no thread, no socket"),
        k("SPARKDL_TPU_ALERTS", "bool", "0", "observe",
          "enable the streaming SLO alert engine in the launcher "
          "monitor loop (alerts.json + alert.* instants)"),
        k("SPARKDL_TPU_ALERT_WINDOW_S", "float", "60", "observe",
          "rolling window for live attribution and alert rules (s)"),
        k("SPARKDL_TPU_ALERT_CHECK_S", "float", "5", "observe",
          "alert rule evaluation cadence (s)"),
        k("SPARKDL_TPU_ALERT_STEP_FACTOR", "float", "2.0", "observe",
          "step-time regression fires at median > factor x baseline"),
        k("SPARKDL_TPU_ALERT_STEP_BASELINE_S", "float", None,
          "observe", "explicit step-time baseline (s); default: "
          "committed ledger record, else self-calibrated"),
        k("SPARKDL_TPU_ALERT_MIN_STEPS", "int", "5", "observe",
          "minimum windowed steps before step/overlap rules judge"),
        k("SPARKDL_TPU_ALERT_MFU_MIN", "float", None, "observe",
          "mfu_drop alert floor (dormant unless set)"),
        k("SPARKDL_TPU_ALERT_OVERLAP_MIN", "float", None, "observe",
          "overlap_drop alert floor (dormant unless set)"),
        k("SPARKDL_TPU_ALERT_QUEUE_GROWTH", "float", None, "observe",
          "queue_depth_growth alert rate floor per second (dormant "
          "unless set)"),
        k("SPARKDL_TPU_ALERT_HBM_FRAC", "float", "0.9", "observe",
          "hbm_high_water alert fraction of hbm_capacity_bytes"),
        k("SPARKDL_TPU_ALERT_HEARTBEAT_GAP_FRAC", "float", "0.5",
          "observe", "heartbeat_gap warns at this fraction of the "
          "stall window"),
        k("SPARKDL_TPU_ALERT_TTFT_P99_S", "float", None, "observe",
          "server_ttft alert bound: fleet p99 time-to-first-token "
          "seconds, estimated from histogram buckets (dormant unless "
          "set)"),
        k("SPARKDL_TPU_ALERT_HBM_LEAK_BYTES_PER_STEP", "float", None,
          "observe", "hbm_leak alert bound: robust per-rank HBM "
          "growth slope in bytes per unit of progress (dormant "
          "unless set)"),
        k("SPARKDL_TPU_ALERT_RSS_GROWTH_BYTES_PER_STEP", "float",
          None, "observe", "host_rss_growth alert bound: robust "
          "per-rank host RSS growth slope in bytes per unit of "
          "progress (dormant unless set)"),

        # -- compile cache ------------------------------------------
        k("SPARKDL_TPU_COMPILE_CACHE_MAX_AOT", "int", None, "compile",
          "AOT entry count cap"),
        k("SPARKDL_TPU_COMPILE_CACHE_MIN_COMPILE_S", "float", None,
          "compile", "minimum compile time worth caching"),
        k("SPARKDL_TPU_COMPILE_CACHE_MIN_BYTES", "int", None,
          "compile", "minimum executable size worth caching"),

        # -- kernels / interop --------------------------------------
        k("SPARKDL_TPU_FLASH_BLOCK", "int", None, "kernels",
          "flash-attention block size override (legacy square tile; "
          "the per-dimension _Q/_KV knobs win when set; unset, the "
          "kernels' tiles are chosen from the shape)"),
        k("SPARKDL_TPU_FLASH_BLOCK_Q", "int", None, "kernels",
          "flash-attention query tile (rows of scores each grid "
          "program owns); read once at import of ops.attention",
          tunable=True, trial_values=(128, 256),
          benches=("attention",), component="compute"),
        k("SPARKDL_TPU_FLASH_BLOCK_KV", "int", None, "kernels",
          "flash-attention key/value tile (K/V stream granularity of "
          "the inner loop); read once at import of ops.attention",
          tunable=True, trial_values=(128, 256),
          benches=("attention",), component="compute"),
        k("SPARKDL_TPU_PAGED_PAGES_PER_BLOCK", "int", "1", "kernels",
          "KV page tiles DMA'd per paged-decode grid step (wider "
          "steps amortize grid overhead at long contexts, cost VMEM)",
          tunable=True, trial_values=(1, 2, 4),
          benches=("serve",)),
        k("SPARKDL_TPU_KERNEL_QUANT_MATMUL", "enum", "auto", "kernels",
          "fused int8/int4 quant-matmul dispatch: auto = pallas "
          "kernel on TPU / XLA dequant elsewhere, off = XLA dequant "
          "everywhere, force_interpret = emulated kernel (CPU "
          "equivalence oracle); unsupported shapes degrade to XLA "
          "loudly", tunable=True, trial_values=("auto", "off"),
          benches=("serve",)),
        k("SPARKDL_TPU_TORCH_DLPACK", "bool", None, "interop",
          "torch interop: force/disable dlpack zero-copy"),

        # -- chaos injection (test-only family) ---------------------
        k("SPARKDL_TPU_CHAOS_KILL_RANK", "int", None, "chaos",
          "rank to kill at the configured step"),
        k("SPARKDL_TPU_CHAOS_KILL_STEP", "int", None, "chaos",
          "step at which the victim dies"),
        k("SPARKDL_TPU_CHAOS_KILL_PHASE", "str", None, "chaos",
          "boot|step kill phase"),
        k("SPARKDL_TPU_CHAOS_KILL_SIGNAL", "int", None, "chaos",
          "signal delivered to the victim"),
        k("SPARKDL_TPU_CHAOS_STALL_STEP", "int", None, "chaos",
          "step at which the victim stalls"),
        k("SPARKDL_TPU_CHAOS_STALL_STEP_RANK", "int", None, "chaos",
          "rank that stalls"),
        k("SPARKDL_TPU_CHAOS_RENDEZVOUS_STALL_S", "float", None,
          "chaos", "rendezvous stall injection"),
        k("SPARKDL_TPU_CHAOS_RENDEZVOUS_STALL_RANK", "int", None,
          "chaos", "rank whose rendezvous stalls"),
        k("SPARKDL_TPU_CHAOS_CP_DROP", "float", None, "chaos",
          "control-frame drop probability"),
        k("SPARKDL_TPU_CHAOS_CP_DELAY_S", "float", None, "chaos",
          "control-frame delay injection"),
        k("SPARKDL_TPU_CHAOS_MUTE_HEARTBEAT", "bool", None, "chaos",
          "suppress a rank's heartbeats"),
        k("SPARKDL_TPU_CHAOS_ONCE_FILE", "path", None, "chaos",
          "fire-once latch file for injections"),
        k("SPARKDL_TPU_CHAOS_LEAK_BYTES_PER_STEP", "int", None,
          "chaos", "host bytes deliberately leaked per step (proves "
          "the leak alert + doctor end to end)"),
        k("SPARKDL_TPU_CHAOS_LEAK_RANK", "int", None, "chaos",
          "rank that leaks (unset = every rank)"),
    ]
    reg = {}
    for knob in knobs:
        if knob.name in reg:
            raise ValueError(f"duplicate knob registration: {knob.name}")
        reg[knob.name] = knob
    return reg


KNOBS = _build()


def all_knobs():
    """Every registered knob, name-sorted."""
    return [KNOBS[n] for n in sorted(KNOBS)]


def get(name):
    """The registered :class:`Knob`, or None."""
    return KNOBS.get(name)


def registered_names():
    return frozenset(KNOBS)


def is_registered(name):
    """Exact member, or a member of a dynamic prefix family."""
    if name in KNOBS:
        return True
    # A family member (SPARKDL_TPU_CHAOS_KILL_RANK) or the family's
    # own stem as it appears at dynamic composition sites
    # ("SPARKDL_TPU_CHAOS_" + hook → the regex sees SPARKDL_TPU_CHAOS).
    return any(name.startswith(p) or p == name + "_"
               for p in PREFIX_FAMILIES)


def tunable_knobs(bench=None):
    """The autotune search space: tunable knobs, optionally restricted
    to those a given trial harness (``serve`` | ``gbdt`` |
    ``attention``) actually honors."""
    out = [kb for kb in all_knobs() if kb.tunable]
    if bench is not None:
        out = [kb for kb in out if bench in kb.benches]
    return out


def read(name, env=None):
    """The knob's current raw value (env wins, else the registered
    default). Unregistered names raise — reading through the registry
    is how call sites stay on the catalog."""
    kb = KNOBS.get(name)
    if kb is None:
        raise KeyError(f"unregistered knob {name!r}; add it to "
                       "sparkdl_tpu.utils.knobs.KNOBS")
    env = os.environ if env is None else env
    v = env.get(name)
    return kb.default if v is None else v


def read_int(name, default=None, env=None):
    """Integer knob via :func:`read`; empty/unset falls back to
    ``default``. A non-integer value raises a ValueError NAMING the
    knob — a ValueError, not SystemExit, because knob reads happen on
    worker/serving threads where SystemExit is silently swallowed and
    ``except Exception`` recovery paths could never catch it."""
    v = read(name, env=env)
    if v in (None, ""):
        return default
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name}={v!r} is not an integer") from None


def read_bool(name, env=None):
    """Boolean knob via :func:`read`: ``0``/``false``/``off``/empty =
    False, anything else (including the registered default) = truthy
    per the same spelling."""
    v = read(name, env=env)
    return str(v or "").strip().lower() not in ("", "0", "false", "off")
