"""Kind ``serve``: ``ContinuousBatchingEngine`` (paged cache) behind
``ServingFrontend`` on loopback, in this process, which holds the chip;
the load generator is a JAX-free child (``chipbench/loadgen.py``) that
streams ``POST /generate`` on the schedule the traffic file and the seed
give.

Outside the window, six requests sent one at a time through the
non-streaming reply warm up every shape the traffic uses (the prefill
buckets and the decode chunk lengths) and are the correctness sample:
the engine's reported log-probabilities against the plain reference's
full forward over prompt + generated tokens.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

from chipbench import loadgen, trace_reduce
from chipbench.common import (
    CompileCounter,
    cache_everything,
    device_facts,
    init_params,
    llama_config,
    require_chips,
)


def build_engine(config, job, seed):
    from sparkdl_tpu.models import Llama
    from sparkdl_tpu.models.serving import ContinuousBatchingEngine

    eng = job["engine"]
    cfg = llama_config(config, max_cache_len=eng["max_cache_len"],
                       paged_kernel=eng["paged_kernel"])
    params = init_params(cfg, seed)
    return ContinuousBatchingEngine(
        Llama(cfg), params, n_slots=eng["n_slots"],
        page_size=eng["page_size"], chunk=eng["chunk"], temperature=0.0)


def post(address, tokens, max_new):
    """One non-streaming ``POST /generate``: (tokens, logprobs)."""
    req = urllib.request.Request(
        f"http://{address[0]}:{address[1]}/generate",
        data=json.dumps({"tokens": [int(t) for t in tokens],
                         "max_new_tokens": int(max_new)}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        reply = json.loads(resp.read())
    return reply["tokens"], reply["logprobs"]


def scrape(address):
    """``GET /metrics`` as text."""
    with urllib.request.urlopen(
            f"http://{address[0]}:{address[1]}/metrics", timeout=60) as resp:
        return resp.read().decode()


def warm_up_and_sample(address, job, seed, vocab_size):
    """Every shape the window will use, one request at a time: a prompt
    in each prefill bucket, and budgets that make the engine run each
    decode chunk length. Returns the (prompt, tokens, logprobs) sample."""
    import numpy as np

    rng = np.random.default_rng(seed)
    sample = []
    for prompt_len, max_new in job["warm_up"]:
        prompt = rng.integers(1, vocab_size, prompt_len)
        tokens, logprobs = post(address, prompt, max_new)
        sample.append((prompt, tokens, logprobs))
    return sample


def reference_check(config, job, params, sample):
    """The engine's reported log-probability of each token it chose
    against the plain reference's full causal forward over prompt +
    generated tokens, one request at a time; and each chosen token's
    reference logit against the reference's largest (greedy decoding
    may turn a near-tie, no more). Logits, not tokens."""
    import numpy as np

    from chipbench import reference

    tol = job["check"]
    arch = reference.arch_of(config)
    width = max(len(p) + len(t) for p, t, _ in sample)
    width = -(-width // 128) * 128          # one shape for the reference
    worst_lp = worst_gap = largest = 0.0
    n = 0
    for prompt, tokens, logprobs in sample:
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(prompt)] = prompt
        padded[0, len(prompt):len(prompt) + len(tokens) - 1] = tokens[:-1]
        cols = len(prompt) - 1 + np.arange(len(tokens))
        logits = np.asarray(reference.logits_at(
            params, padded, np.zeros(len(tokens), np.int32), cols, arch))
        top = logits.max(-1, keepdims=True)
        logp = logits - top - np.log(
            np.exp(logits - top).sum(-1, keepdims=True))
        rows = np.arange(len(tokens))
        worst_lp = max(worst_lp, float(np.abs(
            logp[rows, tokens] - np.asarray(logprobs)).max()))
        worst_gap = max(worst_gap, float(
            (top[:, 0] - logits[rows, tokens]).max()))
        largest = max(largest, float(np.abs(logits).max()))
        n += len(tokens)
    bound = tol["logit_parts"] * largest
    return {"tokens_checked": n, "max_logprob_diff": worst_lp,
            "max_gap_to_largest": worst_gap, "largest_logit": largest,
            "bound": bound,
            "ok": worst_lp <= bound and worst_gap <= 2 * bound}


def _sleep_until(t):
    while True:
        left = t - time.time()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def window(address, engine, job, seed, seconds, out_dir, trace, counter):
    """One measured window against a warm engine: the generator child
    offers the seed's schedule, this process reads the engine's counters
    at the window's edges and, traced, profiles a stretch in its middle.
    Returns what was seen, the client's summary under ``client``."""
    requests = loadgen.make_schedule(job, seed, seconds, engine.cfg.vocab_size)
    deadline_s = seconds + job["drain_limit_s"]
    start_at = time.time() + job["lead_s"]
    schedule_path = os.path.join(out_dir, "schedule.json")
    records_path = os.path.join(out_dir, "records.json")
    with open(schedule_path, "w") as f:
        json.dump({"address": list(address), "start_at": start_at,
                   "deadline_s": deadline_s, "requests": requests}, f)
    child = subprocess.Popen(
        [sys.executable, loadgen.__file__, schedule_path, records_path])
    try:
        metrics_before = scrape(address)
        _sleep_until(start_at)
        t_window = time.perf_counter()
        stats = {"start": dict(engine.stats)}
        reduced = None
        if trace:
            trace_dir = os.path.join(out_dir, "trace")
            _sleep_until(start_at + (seconds - job["traced_s"]) / 2)
            with trace_reduce.profile(trace_dir):
                stats["trace_start"] = dict(engine.stats)
                time.sleep(job["traced_s"])
                stats["trace_end"] = dict(engine.stats)
        _sleep_until(start_at + seconds)
        stats["end"] = dict(engine.stats)
        compiles = counter.since(t_window)
        metrics_after = scrape(address)
        child.wait(timeout=job["drain_limit_s"] + 30)
        if trace:       # after the window: reducing takes the interpreter
            reduced = trace_reduce.reduce_dir(trace_dir)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(records_path) as f:
        records = json.load(f)
    return {
        "client": loadgen.summarize(records, seconds, deadline_s),
        "engine_stats": stats, "compiles_in_window": compiles,
        "trace": reduced, "start_at": start_at,
        "metrics_text": (metrics_before, metrics_after),
        "prompt_tokens": sorted(len(r["tokens"]) for r in requests),
        "output_tokens": sorted(r["max_new"] for r in requests),
    }


def serving(config, job, seed):
    """(engine, frontend, kernels in the decode chunk, check): a warm,
    checked engine behind its frontend. The caller closes the frontend."""
    import jax

    from sparkdl_tpu.models.server import ServingFrontend

    engine = build_engine(config, job, seed)
    kernels = engine.lower_decode_chunk().as_text().count("tpu_custom_call")
    frontend = ServingFrontend(engine).start()
    try:
        with jax.profiler.TraceAnnotation("chipbench.warm_up"):
            sample = warm_up_and_sample(
                frontend.address, job, seed, engine.cfg.vocab_size)
        with jax.profiler.TraceAnnotation("chipbench.reference_check"):
            check = reference_check(config, job, engine.params, sample)
    except BaseException:
        frontend.close()
        raise
    return engine, frontend, kernels, check


def run(spec, *, seed, seconds, trace):
    import jax

    require_chips(jax, spec["cell"]["chips"])
    cache_everything()
    counter = CompileCounter()
    job, config = spec["traffic"], spec["config"]
    engine, frontend, kernels, check = serving(config, job, seed)
    try:
        seen = window(frontend.address, engine, job, seed, seconds,
                      spec["out_dir"], trace, counter)
    finally:
        frontend.close()

    client, reduced = seen["client"], seen["trace"]
    notes = [{"kernels_in_decode_chunk": kernels, "check": check,
              **{k: seen[k] for k in (
                  "compiles_in_window", "engine_stats", "client",
                  "prompt_tokens", "output_tokens")}}]
    run = {
        "correct": (check["ok"] and seen["compiles_in_window"] == 0
                    and kernels >= job["min_kernels"]),
        "attempted": client["attempted"], "failed": client["failed"],
        "end_to_end": {
            "setup_s": seen["start_at"] - spec["started"],
            "ttft_p95_ms": client["ttft_p95_ms"],
            "tpot_p95_ms": client["tpot_p95_ms"],
            "serve_out_tokens_per_s": client["serve_out_tokens_per_s"]},
        "device": device_facts(jax), "notes": notes, "spec": spec, **seen,
    }
    if reduced:
        notes.append({"trace_layout": reduced.pop("layout"),
                      "device_modules": reduced["device_modules"]})
        run["device"] = {**run["device"], "busy_s": reduced["busy_s"],
                         "window_s": reduced["window_s"]}
        run["breakdown"] = {
            "device_ops": ([["module " + m, s] for m, s in
                            reduced["device_modules"][:4]]
                           + reduced["device_ops"])[:10],
            "idle_gaps": [[name.replace(trace_reduce.NO_SPAN,
                                        "engine loop (no span)"), s]
                          for name, s in reduced["idle_gaps"]]}
    return run
