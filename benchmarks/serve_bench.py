#!/usr/bin/env python
"""Latency-under-load gate for the serving stack: N concurrent client
streams against a live frontend — the single-replica
:class:`ServingFrontend` or, with ``--replicas > 1``, the
admission-controlled multi-replica :class:`FleetFrontend` — reporting
p50/p99 time-to-first-token, p50/p99 inter-token latency, goodput, and
aggregate tokens/sec — the ROADMAP item-1 acceptance bench.

Two arrival models (``--mode``):

- ``closed`` (default): each of ``--streams`` clients keeps exactly
  one request in flight, sending the next the moment one finishes —
  the classic closed-loop saturation measurement.
- ``poisson``: open-loop Poisson arrivals at ``--rate`` requests/sec
  across the whole fleet, each request on its own thread regardless of
  how many are already in flight — the overload-behavior measurement
  (closed loops self-throttle and hide queueing collapse). In this
  mode the report SPLITS queue wait from service time
  (arrival→admission vs admission→first-token, scraped from the
  server's own ``server_queue_wait_seconds`` /
  ``server_service_first_token_seconds`` histograms) and counts 503
  admission rejections SEPARATELY — a rejected request is the
  admission controller doing its job, and folding it into the latency
  samples would reward rejecting everything.

Quantized serving: ``--quant int8|int4`` serves every replica through
the weight-only quantized path; ``--ab-quant`` runs the SAME load
twice — bf16 fleet then int8 fleet — and reports the throughput delta
(``serve_int8_speedup``), the ROADMAP acceptance number.

Perf ledger: unless ``--no-ledger``, the run lands as ONE
``history.jsonl`` line (``bench="serve_bench"`` via
``observe.perf.sample_metric``/``history_record``/``append_history``,
exactly like ``attention_bench``), so
``python -m sparkdl_tpu.observe.compare`` can gate regressions against
a committed baseline — ``ci/serve_smoke.py`` does.

With one replica the bench is deliberately ALSO an end-to-end test of
the serving observability layer (ISSUE 6): it exports
``SPARKDL_TPU_TELEMETRY_DIR`` (when unset) so the frontend builds its
:class:`~sparkdl_tpu.observe.serving.ServingTelemetry`, cross-checks
the server's ``/metrics`` against the client-measured numbers, and
validates the run-dir artifacts after ``close()``. Fleet mode records
its SLO histograms on the always-on fleet registry instead (request-id
spaces collide across replicas, so the span-tree layer stays a
single-replica feature).

Prints exactly ONE JSON line on stdout; exits nonzero on null
percentiles, count mismatches, hung requests, or malformed artifacts.
``SPARKDL_TPU_BENCH_TINY=1`` selects a CPU-sized model;
``SPARKDL_TPU_BENCH_PLATFORM=cpu`` pins the jax platform.
"""

import argparse
import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _percentile(values, q):
    """Exact percentile of a non-empty list (numpy is already a hard
    dependency of the model under test)."""
    import numpy as np

    return float(np.percentile(values, q))


# -- Prometheus text parsing (scrape-side of the end-to-end check) ----------


def parse_prom(text):
    """{(name, (label tuples sorted)): value} over every sample line."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$",
                     line)
        if not m:
            continue
        name, _, labels_s, value = m.groups()
        labels = ()
        if labels_s:
            labels = tuple(sorted(
                (k, v) for k, v in re.findall(
                    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"',
                    labels_s)
            ))
        try:
            out[(name, labels)] = float(value)
        except ValueError:
            continue
    return out


def hist_quantile(samples, name, q, extra_labels=()):
    """Histogram quantile estimate from ``<name>_bucket`` cumulative
    counts (linear interpolation inside the bucket; the +Inf bucket
    clamps to the last finite bound). None when the histogram is
    empty or absent."""
    buckets = []
    for (n, labels), v in samples.items():
        if n != name + "_bucket":
            continue
        ld = dict(labels)
        if any(ld.get(k) != val for k, val in extra_labels):
            continue
        le = ld.get("le")
        if le is None:
            continue
        buckets.append((float("inf") if le == "+Inf" else float(le), v))
    if not buckets:
        return None
    buckets.sort()
    total = buckets[-1][1]
    if total <= 0:
        return None
    target = q / 100.0 * total
    prev_upper, prev_cum = 0.0, 0.0
    for upper, cum in buckets:
        if cum >= target:
            if upper == float("inf"):
                return prev_upper  # best we can say: above the range
            if cum == prev_cum:
                return upper
            frac = (target - prev_cum) / (cum - prev_cum)
            return prev_upper + (upper - prev_upper) * frac
        prev_upper, prev_cum = upper, cum
    return prev_upper


# -- client streams ----------------------------------------------------------


class _RequestRecord:
    __slots__ = ("t0", "ttft", "gaps", "tokens", "done_at", "error",
                 "status")

    def __init__(self):
        self.t0 = None
        self.ttft = None
        self.gaps = []
        self.tokens = 0
        self.done_at = None
        self.error = None
        self.status = None    # HTTP status when refused pre-stream


def _stream_one(address, prompt, max_new, rec, timeout):
    """One SSE request, timed client-side: send -> first token (TTFT),
    token -> token (inter-token gaps). A pre-stream HTTP refusal (503
    admission rejection, 400) lands in ``rec.status`` — NOT in the
    latency samples."""
    req = urllib.request.Request(
        f"http://{address[0]}:{address[1]}/generate",
        data=json.dumps({"tokens": prompt, "max_new_tokens": max_new,
                         "stream": True}).encode(),
        headers={"Content-Type": "application/json"},
    )
    rec.t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            last = None
            for line in r:
                line = line.strip()
                if not line.startswith(b"data: "):
                    continue
                ev = json.loads(line[6:])
                now = time.perf_counter()
                if "token" in ev:
                    if last is None:
                        rec.ttft = now - rec.t0
                    else:
                        rec.gaps.append(now - last)
                    last = now
                    rec.tokens += 1
                elif "error" in ev:
                    rec.error = ev["error"]
                elif "done" in ev:
                    rec.done_at = now
    except urllib.error.HTTPError as e:
        rec.status = e.code
        if e.code != 503:     # 503 = admission control, by design
            rec.error = f"HTTP {e.code}: {e.reason}"
    except Exception as e:  # count it, don't kill the bench
        rec.error = str(e)


def drive(address, *, streams, requests_per_stream, mode, rate,
          prompt_len, max_new, vocab, timeout, seed=0):
    """Run the load; returns (records, wall_seconds)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    total = streams * requests_per_stream
    prompts = [rng.integers(1, vocab, (prompt_len,)).astype(int).tolist()
               for _ in range(total)]
    records = [_RequestRecord() for _ in range(total)]
    t_start = time.perf_counter()
    if mode == "closed":
        def client(s):
            for j in range(requests_per_stream):
                i = s * requests_per_stream + j
                _stream_one(address, prompts[i], max_new, records[i],
                            timeout)

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    else:  # poisson open loop: fire at the schedule, never wait
        gaps = rng.exponential(1.0 / rate, size=total)
        threads = []
        for i in range(total):
            time.sleep(float(gaps[i]))
            t = threading.Thread(
                target=_stream_one,
                args=(address, prompts[i], max_new, records[i], timeout))
            t.start()
            threads.append(t)
        for t in threads:
            t.join()
    return records, time.perf_counter() - t_start


# -- run-dir artifact validation --------------------------------------------


def check_artifacts(run_dir, completed):
    """The end-to-end instrumentation check: the run dir the frontend
    wrote on close() must tell the same story the clients measured.
    Returns a list of problems (empty = ok)."""
    problems = []
    tl_path = os.path.join(run_dir, "timeline.json")
    prom_path = os.path.join(run_dir, "metrics.prom")
    try:
        with open(tl_path) as f:
            trace = json.load(f)
    except (OSError, ValueError) as e:
        return [f"unreadable {tl_path}: {e}"]
    spans = [e for e in trace.get("traceEvents", ())
             if isinstance(e, dict) and e.get("name") == "request"
             and e.get("ph") == "X"]
    if len(spans) < completed:
        problems.append(
            f"timeline.json has {len(spans)} request spans, "
            f"expected >= {completed}")
    for ev in spans:
        args = ev.get("args", {})
        if args.get("rid") is None:
            problems.append(f"request span without rid: {ev}")
            break
        if args.get("code") == 200 and args.get("ttft_s") is None:
            problems.append(f"served request span without ttft_s: {ev}")
            break
    try:
        with open(prom_path) as f:
            prom = f.read()
    except OSError as e:
        return problems + [f"unreadable {prom_path}: {e}"]
    for series in ("server_ttft_seconds_count",
                   "server_inter_token_seconds_count",
                   "engine_batch_utilization_count"):
        if series not in prom:
            problems.append(f"metrics.prom missing {series}")
    return problems


# -- one measured load -------------------------------------------------------


def _build_frontend(args, model, params, quant):
    from sparkdl_tpu.models.serving import ContinuousBatchingEngine

    def factory():
        return ContinuousBatchingEngine(
            model, params, n_slots=args.n_slots, chunk=args.chunk,
            page_size=args.page_size, quant=quant)

    if args.replicas > 1:
        from sparkdl_tpu.models.fleet import FleetFrontend

        return FleetFrontend(factory, replicas=args.replicas,
                             max_queue=args.max_queue).start()
    from sparkdl_tpu.models.server import ServingFrontend

    return ServingFrontend(factory()).start()


def run_load(args, model, params, vocab, quant=""):
    """Build a frontend (quantized per ``quant``), warm it, drive the
    configured load, scrape ``/metrics``, close. Returns a result dict
    + list of problems."""
    fe = _build_frontend(args, model, params, quant)
    fleet_mode = args.replicas > 1
    problems = []
    try:
        if not fleet_mode and fe.request_telemetry is None:
            problems.append("frontend built no ServingTelemetry "
                            "(telemetry dir not latched?)")
        # warm: compile the prefill bucket + chunk programs outside
        # the measured window (XLA compile is not a latency SLO).
        # Fleet: one warmup per replica, fired CONCURRENTLY with a
        # small stagger — sequential warmups would all route to the
        # same idle replica (least-depth ties break to the first),
        # leaving the others to pay first-dispatch tracing inside the
        # measured window.
        warms = [_RequestRecord() for _ in range(args.replicas)]
        threads = []
        for warm in warms:
            t = threading.Thread(
                target=_stream_one,
                args=(fe.address, [1] * args.prompt_len, args.max_new,
                      warm, args.timeout))
            t.start()
            threads.append(t)
            time.sleep(0.05)   # let the previous warmup's depth land
        for t in threads:
            t.join()
        for warm in warms:
            if warm.error:
                problems.append(f"warmup request failed: {warm.error}")

        records, wall = drive(
            fe.address, streams=args.streams,
            requests_per_stream=args.requests_per_stream,
            mode=args.mode, rate=args.rate, prompt_len=args.prompt_len,
            max_new=args.max_new, vocab=vocab, timeout=args.timeout,
        )
        done = [r for r in records if r.ttft is not None and not r.error]
        rejected = [r for r in records if r.status == 503]
        # HUNG = the client gave up waiting (urlopen timeout): the one
        # outcome a serving fleet must never produce — classified
        # apart from ordinary failures so the zero-hung gate is real
        hung = [r for r in records
                if r.error and "timed out" in str(r.error).lower()]
        hung_ids = {id(r) for r in hung}
        failed = [r for r in records
                  if (r.error or (r.ttft is None and r.status != 503))
                  and id(r) not in hung_ids]
        ttfts = [r.ttft for r in done]
        gaps = [g for r in done for g in r.gaps]
        total_tokens = sum(r.tokens for r in done)

        # server-side cross-check: scrape /metrics BEFORE close
        with urllib.request.urlopen(
                f"http://{fe.address[0]}:{fe.address[1]}/metrics",
                timeout=60) as r:
            prom = parse_prom(r.read().decode())
        served = args.replicas + len(done)  # warmups included
        # ONE series name for the TTFT SLO on both frontends (the
        # fleet emits it alongside server_first_token_seconds)
        ttft_series = "server_ttft_seconds"
        srv_ttft_count = prom.get((ttft_series + "_count", ()), 0)
        if srv_ttft_count < served:
            problems.append(
                f"{ttft_series}_count {srv_ttft_count} < {served} "
                "served requests — instrumentation dropped requests")
        util_sum = prom.get(("engine_batch_utilization_sum", ()))
        util_count = prom.get(("engine_batch_utilization_count", ()))
        util_avg = (util_sum / util_count if util_sum is not None
                    and util_count else None)
        server = {
            "ttft_count": srv_ttft_count,
            "ttft_p50_s_est": hist_quantile(prom, ttft_series, 50),
            "ttft_p99_s_est": hist_quantile(prom, ttft_series, 99),
            "inter_token_p50_s_est": hist_quantile(
                prom, "server_inter_token_seconds", 50),
            "queue_wait_p50_s_est": hist_quantile(
                prom, "server_queue_wait_seconds", 50),
            "queue_wait_p99_s_est": hist_quantile(
                prom, "server_queue_wait_seconds", 99),
            "generated_tokens": prom.get(
                ("server_generated_tokens_total", ())),
        }
        if fleet_mode:
            # arrival→admission vs admission→first-token: the split
            # that makes admission control's effect visible
            server["service_ttft_p50_s_est"] = hist_quantile(
                prom, "server_service_first_token_seconds", 50)
            server["service_ttft_p99_s_est"] = hist_quantile(
                prom, "server_service_first_token_seconds", 99)
            server["rejections_503"] = sum(
                v for (n, labels), v in prom.items()
                if n == "server_admission_rejections_total")
            server["replica_restarts"] = sum(
                v for (n, labels), v in prom.items()
                if n == "server_replica_restarts_total")
    finally:
        fe.close()

    run_dir = None
    if not fleet_mode:
        run_dir = (fe.request_telemetry.run_dir
                   if fe.request_telemetry is not None else None)
        if run_dir:
            problems += check_artifacts(run_dir, len(done))
        else:
            problems.append("no run dir written")

    out = {
        "requests": len(records),
        "completed": len(done),
        "rejected_503": len(rejected),
        "failed": len(failed),
        "hung": len(hung),
        "ttft_p50_s": (round(_percentile(ttfts, 50), 4)
                       if ttfts else None),
        "ttft_p99_s": (round(_percentile(ttfts, 99), 4)
                       if ttfts else None),
        "inter_token_p50_s": (round(_percentile(gaps, 50), 5)
                              if gaps else None),
        "inter_token_p99_s": (round(_percentile(gaps, 99), 5)
                              if gaps else None),
        "tokens_per_sec": (round(total_tokens / wall, 1)
                           if wall > 0 and total_tokens else None),
        "goodput_rps": (round(len(done) / wall, 3) if wall > 0
                        else None),
        "batch_utilization_avg": (round(util_avg, 4)
                                  if util_avg is not None else None),
        "server": server,
        "run_dir": run_dir,
        "_ttft_samples": ttfts,
        "_gap_samples": gaps,
    }
    if failed or hung:
        out["errors"] = sorted(
            {r.error for r in failed + hung if r.error})[:3]
    if hung:
        problems.append(
            f"{len(hung)} requests HUNG (client-side timeout)")
    if failed:
        problems.append(
            f"{len(failed)}/{len(records)} requests failed")
    if rejected and not fleet_mode:
        # only the admission-controlled fleet 503s by design; a
        # single ServingFrontend answering 503 is a lifecycle fault
        # (loop death / shutdown) and must fail the bench
        problems.append(
            f"{len(rejected)} 503s from a single-replica frontend "
            "(no admission control exists there — that is a fault)")
    for key in ("ttft_p50_s", "ttft_p99_s", "inter_token_p50_s",
                "inter_token_p99_s", "tokens_per_sec",
                "batch_utilization_avg"):
        if out[key] is None:
            problems.append(f"null {key}")
    return out, problems


def _ledger_metrics(result, suffix=""):
    """sample_metric-shaped ledger entries from one load's results
    (client-measured samples, ms units)."""
    from sparkdl_tpu.observe import perf

    metrics = {}
    if result["_ttft_samples"]:
        metrics[f"serve_ttft_ms{suffix}"] = perf.sample_metric(
            [s * 1e3 for s in result["_ttft_samples"]], unit="ms")
    if result["_gap_samples"]:
        metrics[f"serve_inter_token_ms{suffix}"] = perf.sample_metric(
            [s * 1e3 for s in result["_gap_samples"]], unit="ms")
    if result["tokens_per_sec"] is not None:
        metrics[f"serve_tokens_per_sec{suffix}"] = perf.sample_metric(
            [result["tokens_per_sec"]], unit="tokens/sec",
            higher_is_better=True)
    if result["goodput_rps"] is not None:
        metrics[f"serve_goodput_rps{suffix}"] = perf.sample_metric(
            [result["goodput_rps"]], unit="req/sec",
            higher_is_better=True)
    qw = result["server"].get("queue_wait_p50_s_est")
    if qw is not None:
        metrics[f"serve_queue_wait_ms_p50{suffix}"] = {
            "value": round(qw * 1e3, 4), "unit": "ms"}
    return metrics


def _env_int(name, default=None):
    from sparkdl_tpu.utils import knobs

    return knobs.read_int(name, default)


def _knob_str(name):
    from sparkdl_tpu.utils import knobs

    return knobs.read(name) or ""


def main(argv=None):
    # Serving-knob env defaults (registered in sparkdl_tpu.utils.knobs;
    # the surface an autotuned profile pins) — an explicit CLI flag
    # always wins over the profile's env.
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--requests-per-stream", type=int, default=4)
    ap.add_argument("--mode", choices=("closed", "poisson"),
                    default="closed")
    ap.add_argument("--rate", type=float, default=8.0,
                    help="poisson arrivals/sec across the fleet")
    ap.add_argument("--replicas", type=int,
                    default=_env_int("SPARKDL_TPU_SERVE_REPLICAS", 1),
                    help=">1 serves through the multi-replica "
                         "FleetFrontend (admission control + routing)")
    ap.add_argument("--max-queue", type=int,
                    default=_env_int("SPARKDL_TPU_SERVE_MAX_QUEUE"),
                    help="fleet admission bound (queued+in-flight); "
                         "default: 4x total slots")
    ap.add_argument("--quant", choices=("", "int8", "int4"),
                    default=_knob_str("SPARKDL_TPU_SERVE_QUANT"),
                    help="weight-only quantized serving")
    ap.add_argument("--ab-quant", action="store_true",
                    help="run bf16 then int8 under the same load and "
                         "report the throughput delta")
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--max-new", type=int, default=None)
    ap.add_argument("--n-slots", type=int, default=None)
    ap.add_argument("--page-size", type=int,
                    default=_env_int("SPARKDL_TPU_KV_PAGE_SIZE", 0))
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--no-ledger", action="store_true",
                    help="do not append to the history.jsonl ledger")
    ap.add_argument("--capture", action="store_true",
                    help="profile the measured load (the same bounded "
                         "jax_compat.profiler_trace shim the live "
                         "forensics capture uses); the artifact dir "
                         "rides the JSON record as capture_dir")
    args = ap.parse_args(argv)
    if args.quant not in ("", "int8", "int4"):
        # argparse validates `choices` only for explicitly passed
        # flags — an env/profile-sourced default must face the same
        # check instead of detonating at model build
        ap.error(f"SPARKDL_TPU_SERVE_QUANT={args.quant!r} is not one "
                 "of '', 'int8', 'int4'")
    if args.ab_quant and args.quant:
        # --ab-quant runs its OWN pair (bf16 then int8); silently
        # overriding --quant would label the record with a mode that
        # was never measured
        ap.error("--ab-quant and --quant are mutually exclusive")

    # Single-replica mode doubles as the instrumentation's end-to-end
    # test: opt in before the frontend latches, unless the operator
    # already did. (The fleet records on its own always-on registry.)
    if args.replicas == 1:
        os.environ.setdefault(
            "SPARKDL_TPU_TELEMETRY_DIR",
            tempfile.mkdtemp(prefix="sparkdl-serve-bench-"))

    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import Llama, LlamaConfig
    from sparkdl_tpu.observe import perf

    tiny = bool(os.environ.get("SPARKDL_TPU_BENCH_TINY"))
    if tiny:
        cfg = LlamaConfig.tiny(max_cache_len=128)
        args.n_slots = args.n_slots or 4
        args.chunk = 4
        args.prompt_len = args.prompt_len or 8
        args.max_new = args.max_new or 16
    else:
        cfg = LlamaConfig(
            vocab_size=32000, d_model=1024, n_layers=8, n_heads=16,
            n_kv_heads=8, d_ff=4096, dtype=jnp.bfloat16,
            max_cache_len=2048,
        )
        args.n_slots = args.n_slots or 8
        args.chunk = 16
        args.prompt_len = args.prompt_len or 64
        args.max_new = args.max_new or 128
    # decode chunk rides the shape default unless the knob pins it
    args.chunk = _env_int("SPARKDL_TPU_SERVE_DECODE_CHUNK", args.chunk)
    if args.max_queue is None:
        args.max_queue = 4 * args.n_slots * args.replicas
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    # --capture: profile the measured load (warmup + drive + scrape,
    # the region a TTFT regression would hide in) — None-never-raise,
    # so a runtime without the profiler still benches.
    capture_trace = capture_dir = None
    if args.capture:
        from sparkdl_tpu.utils import jax_compat

        target = os.environ.get("SPARKDL_TPU_BENCH_CAPTURE_DIR") \
            or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "results", "xprof-serve-bench")
        capture_trace = jax_compat.profiler_trace(target)
        capture_dir = capture_trace.__enter__()
    try:
        result, problems = run_load(args, model, params,
                                    cfg.vocab_size,
                                    quant="" if args.ab_quant
                                    else args.quant)
    finally:
        if capture_trace is not None:
            capture_trace.__exit__(None, None, None)
    metrics = _ledger_metrics(result)
    ab = None
    if args.ab_quant:
        int8_result, int8_problems = run_load(
            args, model, params, cfg.vocab_size, quant="int8")
        problems += [f"int8: {p}" for p in int8_problems]
        metrics.update(_ledger_metrics(int8_result, suffix="_int8"))
        speedup = None
        if (result["tokens_per_sec"] and int8_result["tokens_per_sec"]):
            speedup = round(int8_result["tokens_per_sec"]
                            / result["tokens_per_sec"], 4)
            metrics["serve_int8_speedup"] = {
                "value": speedup, "unit": "x",
                "higher_is_better": True}
        ab = {
            "bf16_tokens_per_sec": result["tokens_per_sec"],
            "int8_tokens_per_sec": int8_result["tokens_per_sec"],
            "int8_speedup": speedup,
            "int8": {k: v for k, v in int8_result.items()
                     if not k.startswith("_")},
        }

    # Memory high waters (observe.mem): device peak where the backend
    # reports allocator stats (live-buffer fallback keeps the CPU
    # proxy non-null) and host RSS high water — the serving-side leak
    # ledger the rss-growth alert rule judges against.
    from sparkdl_tpu.observe import mem as mem_acct

    hbm_high_water = mem_acct.device_peak_bytes()
    host_rss_high_water = mem_acct.host_rss_high_water_bytes()

    history = None
    if not args.no_ledger:
        rec = perf.history_record(
            metrics, device_kind=perf.device_kind(),
            bench="serve_bench",
            extra={"mode": args.mode, "streams": args.streams,
                   "replicas": args.replicas,
                   "quant": args.quant or ("ab" if args.ab_quant
                                           else "bf16"),
                   "hbm_high_water_bytes": hbm_high_water,
                   "host_rss_high_water_bytes": host_rss_high_water})
        history = perf.append_history(rec)

    record = {
        "metric": "serve_latency_under_load",
        "mode": args.mode,
        "streams": args.streams,
        "replicas": args.replicas,
        "max_queue": args.max_queue,
        "quant": "ab" if args.ab_quant else args.quant,
        "n_slots": args.n_slots,
        "chunk": args.chunk,
        "prompt_len": args.prompt_len,
        "max_new_tokens": args.max_new,
        "platform": jax.devices()[0].platform,
        "hbm_high_water_bytes": hbm_high_water,
        "host_rss_high_water_bytes": host_rss_high_water,
        "history": history,
        **({"capture_dir": capture_dir} if args.capture else {}),
    }
    record.update(
        {k: v for k, v in result.items() if not k.startswith("_")})
    if args.mode == "poisson":
        record["rate"] = args.rate
    if ab is not None:
        record["ab_quant"] = ab
    if problems:
        record["problems"] = problems
    print(json.dumps(record), flush=True)
    if problems:
        for p in problems:
            print(f"serve_bench: FAIL: {p}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
