"""The benchmark's own tests: fast, on the CPU, no gang and no chip.

They hold the yardstick (generator, trace reduction, operation counts,
reference) to hand-made answers at small sizes, and show that a cell, a
configuration and a per-layer metric are found by name.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, loadgen, reference, trace_reduce
from chipbench import run as harness
from chipbench.common import peaks_for

ROOT = harness.ROOT
BENCH = harness.load_json(ROOT, "BENCHMARK.json")
# entries of the cells built and rehearsed but not yet proved on the chip
STAGED = harness.load_json(ROOT, "chipbench", "staged.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"_size$|intermediate|latent|state|proj|_dim$|_rank$"
                   r"|expansion|experts_per_tok")

TINY = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "intermediate_size": 128, "vocab_size": 256,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5}


def with_staged(bench=BENCH, staged=STAGED):
    """BENCHMARK.json as it stands once every staged entry has moved in."""
    bench = json.loads(json.dumps(bench))
    bench["configs"] += staged["configs"]
    bench["workloads"] += staged["workloads"]
    for key in ("end_to_end", "per_layer"):
        have = {m["name"]: m for m in bench[key]}
        for entry in staged[key]:
            if "add_workloads" in entry:
                have[entry["name"]]["workloads"] += entry["add_workloads"]
            else:
                bench[key].append(dict(entry))
    return bench


@pytest.fixture(scope="module")
def staged_root(tmp_path_factory):
    """A root whose BENCHMARK.json holds the staged cells too; the
    benchmark's own files are the checkout's."""
    root = tmp_path_factory.mktemp("staged")
    os.symlink(os.path.join(ROOT, "chipbench"), root / "chipbench")
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(with_staged(), f)
    return str(root)


def tiny_config(name):
    config = harness.load_json(ROOT, "chipbench", "configs", name + ".json")
    return {**config, **TINY}


# -- BENCHMARK.json and what it names ----------------------------------------


def test_benchmark_json_has_the_contract_keys_and_well_formed_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    assert all(0.01 <= m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize(
    "cell", [w["name"] for w in BENCH["workloads"] + STAGED["workloads"]])
def test_cell_resolves_by_name(cell, staged_root):
    staged = cell not in {w["name"] for w in BENCH["workloads"]}
    spec = harness.load_cell(cell, root=staged_root if staged else ROOT)
    kind = harness.load_kind(spec["traffic"]["kind"])
    assert callable(kind.run)
    assert spec["config"]["name"] == spec["cell"]["config"]
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        # the ONE end-to-end metric it moves is reported where it is
        assert m["moves"] in reported, (m["name"], m["moves"])


@pytest.mark.parametrize("config", BENCH["configs"] + STAGED["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file_states_its_cuts(config):
    data = harness.load_json(ROOT, config["file"])
    assert data["source"] == config["source"] and len(config["source"]) <= 200
    assert sorted(data["reduced"]) == sorted(config["reduced"])
    assert not any(WIDTH.search(key) for key in config["reduced"])
    for key, cut in data["reduced"].items():
        assert data[key] == cut["to"] != cut["from"]
    assert set(data["maps_to"].values()) <= set(data)
    assert any(w["config"] == config["name"]
               for w in BENCH["workloads"] + STAGED["workloads"])


def test_unknown_device_kind_is_an_error():
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")
    assert peaks_for(peaks, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(RuntimeError, match="no row"):
        peaks_for(peaks, "TPU v9")


def test_a_cell_a_configuration_and_a_metric_are_added_as_files(tmp_path):
    """New files and one entry each in BENCHMARK.json: no edit to a file
    that is there."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(os.path.join(root, "chipbench"))
              for p in fs}
    bench = json.loads(json.dumps(BENCH))
    config = {**tiny_config("mistral-7b-v0.3"), "name": "new-model"}
    with open(os.path.join(root, "chipbench/configs/new-model.json"), "w") as f:
        json.dump(config, f)
    traffic = harness.load_json(
        ROOT, "chipbench", "traffic", "lora-train-4x2048.json")
    with open(os.path.join(root, "chipbench/traffic/new-job.json"), "w") as f:
        json.dump({**traffic, "seq": 8192, "batch": 1}, f)
    with open(os.path.join(root, "chipbench/readers/new_count.train.py"),
              "w") as f:
        f.write("def read(run):\n    return run.get('steps')\n")
    bench["configs"].append({
        "name": "new-model", "source": config["source"], "reduced": [],
        "file": "chipbench/configs/new-model.json", "why": "a test"})
    bench["workloads"].append({
        "name": "new-cell", "config": "new-model", "traffic": "new-job",
        "chips": 1, "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append("new-cell")
    bench["per_layer"].append({
        "name": "new_count.train", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["new-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    spec = harness.load_cell("new-cell", root=root)
    assert spec["traffic"]["seq"] == 8192
    assert spec["config"]["hidden_size"] == 64
    assert [m["name"] for m in spec["per_layer"]] == ["new_count.train"]
    run = {"correct": True, "attempted": 3, "failed": 0, "steps": 3,
           "device": {"platform": "tpu"}, "end_to_end": {}}
    line = harness.result_line(spec, run, trace=1)
    assert line["metrics"] == {"new_count.train": {"value": 3, "unit": "steps"}}
    # a reader that finds nothing returns nothing: left out of the line
    del run["steps"]
    assert harness.result_line(spec, run, trace=1)["metrics"] == {}
    after = {p: open(os.path.join(dp, p)).read()
             for dp, _, fs in os.walk(os.path.join(root, "chipbench"))
             for p in fs if p in before}
    assert after == before


def test_staged_entries_keep_the_contract_once_moved_in():
    bench = with_staged()
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    assert all(len(w["why"]) <= 200 for w in bench["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells and UNIT.match(m["unit"])
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    # no staged bound is a number: a benchmark PR sets each from its runs
    assert all(m.get("bound") is None for m in STAGED["end_to_end"])


def test_serve_kind_refuses_to_run_without_a_tpu(staged_root):
    from chipbench.common import NoChip
    from chipbench.kinds import serve

    spec = harness.load_cell("mistral7b-serve-chat", root=staged_root)
    with pytest.raises(NoChip, match="TPU"):
        serve.run(spec, seed=1, seconds=1.0, trace=False)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cli_exits_nonzero_and_prints_no_result_without_a_tpu(cell):
    done = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr


# -- the load generator ------------------------------------------------------


def _traffic():
    return harness.load_json(ROOT, "chipbench", "traffic", "chat-poisson.json")


def test_schedule_repeats_for_a_seed_and_replays_the_same_cycle_for_another():
    traffic = _traffic()
    big = 2**31 + 12345     # the driver's seeds are large
    a = loadgen.make_schedule(traffic, big, 10.0, 32768)
    b = loadgen.make_schedule(traffic, big, 10.0, 32768)
    c = loadgen.make_schedule(traffic, 7, 10.0, 32768)
    assert a == b and a != c
    assert len(a) == round(traffic["rate_per_s"] * 10.0)

    def cycle(schedule):    # (prompt, answer, gap to the next) in order
        due = [r["due"] for r in schedule] + [10.0]
        return [(len(r["tokens"]), r["max_new"], round(due[i + 1] - due[i], 9))
                for i, r in enumerate(schedule)]

    # another seed starts the same cycle at another request
    ca, cc = cycle(a), cycle(c)
    assert ca != cc and any(ca[k:] + ca[:k] == cc for k in range(len(ca)))
    assert a[0]["tokens"] != c[cc.index(ca[0])]["tokens"]   # fresh token ids


def test_schedule_keeps_to_the_window_and_the_clips():
    traffic = _traffic()
    schedule = loadgen.make_schedule(traffic, 3, 30.0, 1000)
    due = [r["due"] for r in schedule]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 30.0
    lo, hi = traffic["prompt_tokens"]["clip"]
    assert all(lo <= len(r["tokens"]) <= hi for r in schedule)
    lo, hi = traffic["output_tokens"]["clip"]
    assert all(lo <= r["max_new"] <= hi for r in schedule)
    assert all(1 <= t < 1000 for r in schedule for t in r["tokens"])
    prompts = [len(r["tokens"]) for r in schedule]
    assert np.median(prompts) > np.median([r["max_new"] for r in schedule])


def test_summarize_counts_from_due_and_a_failure_misses():
    records = [
        {"due": 0.0, "max_new": 3, "sent": 0.001, "error": None,
         "token_at": [0.1, 0.2, 0.3]},
        {"due": 1.0, "max_new": 2, "sent": 1.5, "error": None,
         "token_at": [2.0, 2.5]},
        {"due": 2.0, "max_new": 4, "sent": 2.0, "error": None,
         "token_at": [3.0, 11.0]},                       # never finished
        {"due": 3.0, "max_new": 1, "sent": None,
         "error": "ConnectionRefusedError", "token_at": []},
    ]
    seen = loadgen.summarize(records, seconds=10.0, deadline_s=12.0)
    assert (seen["attempted"], seen["failed"]) == (4, 2)
    # ttft: 0.1, 1.0 (from DUE, not from sent), and the misses 10, 9
    assert seen["ttft_p50_ms"] == pytest.approx(1e3 * (1.0 + 9.0) / 2)
    assert seen["tpot_p95_ms"] == pytest.approx(1e3 * (0.1 + 0.95 * 0.4))
    assert seen["serve_out_tokens_per_s"] == pytest.approx(6 / 10.0)
    assert seen["backlog_mid"] == 2 and seen["backlog_end"] == 2
    assert loadgen.percentile([1, 2, 3, 4], 50) == 2.5


# -- the trace reduction -----------------------------------------------------


def test_interval_arithmetic():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        [0, 3], [5, 8]]
    assert trace_reduce.subtract([[0, 10]], [[2, 3], [5, 12]]) == [
        [0, 2], [3, 5]]
    assert trace_reduce.subtract([[0, 4], [6, 9]], []) == [[0, 4], [6, 9]]


def test_trace_reduce_on_a_synthetic_trace():
    ms = 1_000_000
    devices = {0: {
        "ops": [("fusion.1", 0, 40 * ms), ("flash_fwd", 30 * ms, 20 * ms),
                ("all-reduce.2", 60 * ms, 10 * ms),
                ("fusion.1", 65 * ms, 10 * ms),
                ("fusion.9", 150 * ms, 10 * ms)],      # outside the window
        "modules": [("jit_step(42)", 0, 75 * ms), ("jit_step(43)", 150 * ms, 10 * ms)],
    }}
    spans = [(trace_reduce.WINDOW_SPAN, 0, 100 * ms),
             ("chipbench.step", 0, 58 * ms),
             ("chipbench.next_batch", 50 * ms, 8 * ms),
             ("python thing", 0, 100 * ms)]
    spans = [s for s in spans if s[0].startswith(trace_reduce.SPAN_PREFIX)]
    got = trace_reduce.reduce_events(devices, spans)
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx(0.065)         # 0-50 and 60-75
    assert got["collective_exposed_s"] == pytest.approx(0.005)   # 60-65
    assert got["modules_s"] == {"jit_step": pytest.approx(0.075)}
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(0.050)]
    # the gap 50-60 lies under next_batch (the innermost span that
    # covers its middle), the gap 75-100 under none
    assert dict(map(tuple, got["idle_gaps"])) == {
        "chipbench.next_batch": pytest.approx(0.010),
        trace_reduce.NO_SPAN: pytest.approx(0.025)}
    two = trace_reduce.reduce_events({0: devices[0], 1: {"ops": []}}, spans)
    assert two["busy_s"] == pytest.approx(0.0325)        # mean over chips


# -- operations from shapes --------------------------------------------------


def test_flops_against_hand_counts_mistral():
    cfg = harness.load_json(ROOT, "chipbench/configs/mistral-7b-v0.3.json")
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2             # q, o; k, v
    layer = attn + 3 * 4096 * 14336
    assert flops.layer_matmul_params(cfg) == layer == 218_103_808
    assert flops.model_params(cfg) == (
        16 * (layer + 2 * 4096) + 2 * 32768 * 4096 + 4096)   # 3.76 B
    adapters = 8 * (4096 + 4096) + 8 * (4096 + 1024)     # q, v
    assert flops.lora_adapter_params(cfg, 8, ["q_proj", "v_proj"]) == adapters
    attention = 6 * 2 * 32 * 128 * (2048 + 1) / 2
    want = (4 * (16 * layer + 32768 * 4096) + 6 * 16 * adapters
            + 16 * attention)
    assert flops.lora_train_flops_per_token(
        cfg, 2048, rank=8, targets=["q_proj", "v_proj"]) == want
    assert 1.5e10 < want < 1.6e10


def test_flops_mixtral_counts_the_two_experts_a_token_uses_not_the_eight():
    cfg = harness.load_json(ROOT, "chipbench/configs/mixtral-8x7b-v0.1.json")
    attn = 4096 * 4096 * 2 + 4096 * 1024 * 2
    expert = 3 * 4096 * 14336
    assert flops.layer_matmul_params(cfg) == attn + 4096 * 8 + 2 * expert
    assert flops.layer_params(cfg) == (
        attn + 8 * expert + 4096 * 8 + 8 + 2 * 4096)     # 1.45 B held
    assert 1.44e9 < flops.layer_params(cfg) < 1.46e9
    dense = dict(cfg, num_local_experts=0)
    assert (flops.layer_matmul_params(cfg)
            - flops.layer_matmul_params(dense)) == expert + 4096 * 8


def test_kernel_costs_and_roofline():
    cfg = harness.load_json(ROOT, "chipbench/configs/mistral-7b-v0.3.json")
    peaks = peaks_for(harness.load_json(ROOT, "chipbench/peaks.json"),
                      "TPU v5 lite")
    ops, nbytes = flops.flash_attention_cost(cfg, 4, 2048, backward=False)
    assert ops == 4 * 2048 * 2 * 2 * 32 * 128 * 2049 / 2
    assert nbytes == 4 * (4 * 2048 * 32 * 128 * 2)
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "compute"
    ops, nbytes = flops.paged_decode_cost(cfg, [17, 160], 16)
    assert ops == 4 * 32 * 128 * (17 + 160)
    assert nbytes == (2 + 10) * 16 * 8 * 128 * 2 * 2     # whole pages, K and V
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"


# -- the reference and the checks --------------------------------------------


@pytest.mark.parametrize("config", ["mistral-7b-v0.3", "mixtral-8x7b-v0.1"])
def test_reference_agrees_with_the_program_at_a_tiny_size(config):
    from chipbench.common import llama_config
    from sparkdl_tpu.models import Llama
    from sparkdl_tpu.parallel.train import cross_entropy_loss

    hf = tiny_config(config)
    if "num_local_experts" in hf:
        hf["num_local_experts"] = 4
    cfg = llama_config(hf, dtype=jnp.float32, lora_rank=8)
    model = Llama(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    targets = jnp.roll(tokens, -1, 1)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    params = jax.tree_util.tree_map_with_path(   # adapters that do something
        lambda p, x: x + 0.01 if "lora_b" in jax.tree_util.keystr(p) else x,
        params)
    arch = reference.arch_of(hf)

    def loss(p):
        return cross_entropy_loss(model.apply({"params": p}, tokens), targets)

    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, tokens)
        want_loss, grads = jax.value_and_grad(loss)(params)
    rows, cols = np.repeat(np.arange(2), 24), np.tile(np.arange(24), 2)
    got = reference.logits_at(params, tokens, rows, cols, arch)
    np.testing.assert_allclose(
        np.asarray(got).reshape(logits.shape), logits, atol=2e-5)
    norm = np.sqrt(sum(
        float(jnp.sum(g * g))
        for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]
        if "lora_" in jax.tree_util.keystr(p)))
    got_loss, got_norm = reference.loss_and_adapter_grad_norm(
        params, tokens, targets, arch)
    assert got_loss == pytest.approx(float(want_loss), rel=1e-5)
    assert got_norm == pytest.approx(norm, rel=1e-4)


def test_train_kind_checks_and_measures_at_a_tiny_size():
    from chipbench.kinds import train
    from sparkdl_tpu.parallel.train import global_batch

    hf = tiny_config("mixtral-8x7b-v0.1")
    hf["num_local_experts"] = 4
    job = harness.load_json(
        ROOT, "chipbench", "traffic", "lora-train-4x2048.json")
    job = {**job, "batch": 2, "seq": 32,
           "check": {"loss_rtol": 5e-3, "grad_norm_rtol": 5e-2}}
    cfg, params, mask, loss_fn, opt, step = train.setup(hf, job, seed=2**31 + 5)
    assert params["lm_head"]["kernel"].dtype == jnp.bfloat16
    assert params["layer_0"]["attn"]["q_proj"]["lora_a"].dtype == jnp.float32
    batch = jax.tree.map(jnp.asarray, global_batch(
        np.random.default_rng(0), cfg.vocab_size, 2, 32))
    step = jax.jit(step)
    state = step(params, opt.init(params), batch)[:2]        # compiles
    state, losses, elapsed = train.measure(
        step, state, [batch], lambda seconds, steps: steps >= 5)
    assert len(losses) == 5 and elapsed > 0 and losses[-1] < losses[0]
    check = train.reference_check(hf, job, state[0], batch, loss_fn, mask)
    assert check["ok"], check
    tight = {**job, "check": {"loss_rtol": 1e-9, "grad_norm_rtol": 1e-9}}
    assert not train.reference_check(
        hf, tight, state[0], batch, loss_fn, mask)["ok"]


def test_serve_check_holds_the_engine_to_the_reference_at_a_tiny_size():
    from chipbench.kinds import serve

    hf = tiny_config("mistral-7b-v0.3")
    job = _traffic()
    job["engine"] = {**job["engine"], "n_slots": 2, "max_cache_len": 64,
                     "paged_kernel": "off"}
    engine = serve.build_engine(hf, job, seed=3)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, n) for n in (5, 17, 33)]
    rids = [engine.submit(p, 6) for p in prompts]
    tokens = engine.run()
    sample = [(p, tokens[r].tolist(), engine.logprobs[r].tolist())
              for p, r in zip(prompts, rids)]
    check = serve.reference_check(hf, job, engine.params, sample)
    assert check["ok"] and check["tokens_checked"] == 18, check
    wrong = [(p, t, [x - 1.0 for x in lp]) for p, t, lp in sample]
    assert not serve.reference_check(hf, job, engine.params, wrong)["ok"]
    # a token the reference finds far from its largest is no near-tie
    worst = [(p, [int(np.argmin(np.abs(np.arange(256) - 7)))] * len(t), lp)
             for p, t, lp in sample]
    assert not serve.reference_check(hf, job, engine.params, worst)["ok"]


# -- the readers -------------------------------------------------------------


def test_readers_on_a_made_up_run():
    spec = harness.load_cell("mistral7b-lora-train")
    run = {"spec": spec, "device": {"kind": "TPU v5 lite"},
           "end_to_end": {"train_tokens_per_s_per_chip": 6000.0},
           "launch_s": 21.5, "compile_s": 4.0,
           "trace": {"busy_s": 2.94, "window_s": 3.0}}
    per_token = flops.lora_train_flops_per_token(
        spec["config"], 2048, rank=8, targets=["q_proj", "v_proj"])
    assert harness.load_reader("mfu_pct.train")(run) == pytest.approx(
        100 * per_token * 6000 / 197e12)
    assert harness.load_reader("device_idle_pct.train")(run) == pytest.approx(2.0)
    assert harness.load_reader("launch_s.train")(run) == 21.5
    assert harness.load_reader("compile_s.train")(run) == 4.0
    assert harness.load_reader("mfu_pct.train")({}) is None

    text = ("server_first_token_seconds_sum {}\n"
            "server_first_token_seconds_count {}\n")
    serve_run = {
        "engine_stats": {
            "start": {"steps": 10, "active_slot_steps": 40, "total_slot_steps": 320},
            "trace_start": {"steps": 100}, "trace_end": {"steps": 500},
            "end": {"steps": 1010, "active_slot_steps": 16040,
                    "total_slot_steps": 32320}},
        "trace": {"busy_s": 3.0, "window_s": 5.0,
                  "modules_s": {"jit_decode_chunk": 4.8, "jit_paged_prefill": 0.5}},
        "metrics_text": (text.format(1.5, 6), text.format(31.5, 106)),
        "client": {"late_p95_ms": 0.7}}
    read = lambda name: harness.load_reader(name)(serve_run)
    assert read("slot_occupancy_pct.serve") == pytest.approx(50.0)
    assert read("decode_step_ms.serve") == pytest.approx(12.0)
    assert read("server_ttft_mean_ms.serve") == pytest.approx(300.0)
    assert read("loadgen_late_p95_ms.serve") == 0.7
    assert read("device_idle_pct.serve") == pytest.approx(40.0)
    assert harness.load_reader("decode_step_ms.serve")({}) is None
