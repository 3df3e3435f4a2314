#!/usr/bin/env python
"""CI serving latency-under-load smoke (ISSUE 6, extended by ISSUE
11): drive ``benchmarks/serve_bench.py`` with a tiny CPU model in two
steps and FAIL the build when the serving tier misbehaves.

Step 1 — single replica, closed loop, 4 streams (the ISSUE-6
contract): non-null SLO numbers, run-dir artifacts present and
well-formed, and ``observe.doctor`` reads the serving run dir.

Step 2 — the ISSUE-11 fleet contract: **32 concurrent streams** (an
order of magnitude over step 1) under **open-loop poisson** load
against a **2-replica** admission-controlled fleet, run as an
int8-vs-bf16 A/B. Asserts:

- zero hung requests and zero failures (rejected-with-503 is admission
  control working, and is reported separately — but this load is sized
  to admit everything);
- bounded p99 TTFT and inter-token latency
  (``SPARKDL_TPU_SERVE_SMOKE_TTFT_P99_S`` /
  ``_INTER_TOKEN_P99_S`` override the bounds);
- the run landed as a ``history.jsonl`` ledger line, and
  ``python -m sparkdl_tpu.observe.compare`` passes it against the
  committed baseline (``benchmarks/results/serve_baseline.json``) —
  the same noise-aware gate ``attention_bench`` rides;
- the int8-vs-bf16 throughput delta is present in the ledger record.

Usage: ``SPARKDL_TPU_TELEMETRY_DIR=<dir> python ci/serve_smoke.py``
(defaults the dir to ``./serve-artifacts``). Runs outside the
time-boxed tier-1 pytest gate — its own workflow step.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "benchmarks", "results",
                        "serve_baseline.json")

TTFT_P99_BOUND_S = float(os.environ.get(
    "SPARKDL_TPU_SERVE_SMOKE_TTFT_P99_S", "30"))
INTER_TOKEN_P99_BOUND_S = float(os.environ.get(
    "SPARKDL_TPU_SERVE_SMOKE_INTER_TOKEN_P99_S", "5"))


def fail(msg):
    print(f"SERVE SMOKE FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def run_bench(env, extra_args, history_path, timeout=1200):
    env = dict(env)
    env["SPARKDL_TPU_PERF_HISTORY"] = history_path
    r = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "serve_bench.py")]
        + extra_args,
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    sys.stderr.write(r.stderr[-4000:])
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    if len(lines) != 1:
        fail(f"expected exactly one JSON line, got {len(lines)}: "
             f"{r.stdout[-1000:]}")
    try:
        rec = json.loads(lines[0])
    except ValueError as e:
        fail(f"unparseable bench output: {e}: {lines[0][:400]}")
    return r.returncode, rec, lines[0]


def main():
    out_dir = os.environ.setdefault(
        "SPARKDL_TPU_TELEMETRY_DIR",
        os.path.join(os.getcwd(), "serve-artifacts"),
    )
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    env.setdefault("SPARKDL_TPU_BENCH_TINY", "1")
    env.setdefault("JAX_PLATFORMS", "cpu")
    history_path = os.path.join(out_dir, "serve-history.jsonl")

    # ---- step 1: single replica, closed loop, artifacts + doctor ----
    rc, rec, line = run_bench(
        env, ["--streams", "4", "--requests-per-stream", "2",
              "--max-new", "12"], history_path)
    with open(os.path.join(out_dir, "serve-bench.json"), "w") as f:
        f.write(line + "\n")
    if rc != 0:
        fail(f"serve_bench exited {rc}: {rec.get('problems')}")
    for key in ("ttft_p50_s", "ttft_p99_s", "inter_token_p50_s",
                "inter_token_p99_s", "tokens_per_sec",
                "batch_utilization_avg"):
        if not isinstance(rec.get(key), (int, float)):
            fail(f"null/missing {key} in {line[:400]}")
    if rec["completed"] != rec["requests"]:
        fail(f"only {rec['completed']}/{rec['requests']} completed")

    run_dir = rec.get("run_dir")
    if not run_dir or not os.path.isdir(run_dir):
        fail(f"run dir missing: {run_dir!r}")
    for name in ("timeline.json", "metrics.prom", "metrics.json"):
        path = os.path.join(run_dir, name)
        if not os.path.exists(path) or os.path.getsize(path) == 0:
            fail(f"missing/empty artifact {path}")
    with open(os.path.join(run_dir, "timeline.json")) as f:
        trace = json.load(f)
    spans = [e for e in trace.get("traceEvents", ())
             if isinstance(e, dict) and e.get("name") == "request"
             and e.get("ph") == "X"]
    if len(spans) < rec["completed"]:
        fail(f"timeline has {len(spans)} request spans for "
             f"{rec['completed']} completed requests")

    # the doctor must read a serving run dir and exit 0 (no hang);
    # keep its report with the artifacts
    d = subprocess.run(
        [sys.executable, "-m", "sparkdl_tpu.observe.doctor", run_dir],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=REPO,
    )
    with open(os.path.join(out_dir, "doctor-report.txt"), "w") as f:
        f.write(d.stdout + d.stderr)
    if d.returncode != 0:
        fail(f"doctor exited {d.returncode} on the serving run dir:\n"
             f"{d.stdout}\n{d.stderr}")
    if "serving:" not in d.stdout:
        fail(f"doctor report lacks the serving section:\n{d.stdout}")
    print("serve smoke step 1 OK:", json.dumps({
        k: rec[k] for k in ("ttft_p50_s", "ttft_p99_s",
                            "inter_token_p50_s", "inter_token_p99_s",
                            "tokens_per_sec", "batch_utilization_avg")
    }))

    # ---- step 2: 32-stream poisson against a 2-replica fleet --------
    rc, fleet, line = run_bench(
        env, ["--replicas", "2", "--streams", "32",
              "--requests-per-stream", "1", "--mode", "poisson",
              "--rate", "16", "--max-new", "12", "--ab-quant"],
        history_path)
    with open(os.path.join(out_dir, "serve-fleet-bench.json"),
              "w") as f:
        f.write(line + "\n")
    if rc != 0:
        fail(f"fleet serve_bench exited {rc}: "
             f"{fleet.get('problems')}")
    if fleet["streams"] < 32 or fleet["replicas"] < 2:
        fail(f"fleet run under-sized: {fleet['streams']} streams, "
             f"{fleet['replicas']} replicas")
    # zero hung (client-side timeouts) and zero failures — this load
    # is sized so everything admits and completes
    if fleet.get("hung"):
        fail(f"{fleet['hung']} HUNG requests: {fleet.get('errors')}")
    if fleet["failed"]:
        fail(f"{fleet['failed']} failed requests: "
             f"{fleet.get('errors')}")
    if fleet["completed"] + fleet["rejected_503"] != fleet["requests"]:
        fail(f"unaccounted requests: {fleet['completed']} completed + "
             f"{fleet['rejected_503']} rejected != "
             f"{fleet['requests']}")
    # bounded tail latency under open-loop load
    if fleet["ttft_p99_s"] > TTFT_P99_BOUND_S:
        fail(f"p99 TTFT {fleet['ttft_p99_s']}s exceeds the "
             f"{TTFT_P99_BOUND_S}s bound")
    if fleet["inter_token_p99_s"] > INTER_TOKEN_P99_BOUND_S:
        fail(f"p99 inter-token {fleet['inter_token_p99_s']}s exceeds "
             f"the {INTER_TOKEN_P99_BOUND_S}s bound")
    # the queue-wait/service split and the int8 delta must be present
    if fleet["server"].get("queue_wait_p50_s_est") is None:
        fail("poisson fleet run lacks the queue-wait split")
    if not fleet.get("ab_quant", {}).get("int8_speedup"):
        fail(f"no int8-vs-bf16 delta in {line[:400]}")
    # the run must have landed in the ledger...
    if fleet.get("history") != history_path:
        fail(f"fleet run did not land in the ledger: "
             f"{fleet.get('history')!r}")
    # ...and pass the noise-aware compare gate against the committed
    # baseline. --floor 0.5: the CPU-proxy serving numbers are shared-
    # runner noisy; the gate catches collapse (2x), not jitter.
    cmp_report = os.path.join(out_dir, "serve-compare.json")
    c = subprocess.run(
        [sys.executable, "-m", "sparkdl_tpu.observe.compare",
         BASELINE, history_path, "--floor", "0.5",
         "--format", "json"],
        env=env, capture_output=True, text=True, timeout=120,
        cwd=REPO,
    )
    with open(cmp_report, "w") as f:
        f.write(c.stdout + c.stderr)
    if c.returncode != 0:
        fail(f"observe.compare gate failed (rc={c.returncode}) vs "
             f"{BASELINE}:\n{c.stdout}\n{c.stderr}")

    print("serve smoke step 2 OK:", json.dumps({
        "streams": fleet["streams"], "replicas": fleet["replicas"],
        "completed": fleet["completed"],
        "rejected_503": fleet["rejected_503"],
        "ttft_p99_s": fleet["ttft_p99_s"],
        "inter_token_p99_s": fleet["inter_token_p99_s"],
        "queue_wait_p50_s": fleet["server"]["queue_wait_p50_s_est"],
        "int8_speedup": fleet["ab_quant"]["int8_speedup"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
