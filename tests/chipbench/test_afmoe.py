"""The ``train_afmoe`` kind and what it brings (its count of required
work, its readers, its configuration) at a tiny size on the CPU: no
gang, no chip. The program's own tests are in
``tests/models/test_mixed_attention.py`` and, for the windowed kernels,
``tests/ops/test_flash_attention.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, flops_afmoe
from chipbench import run as harness
from chipbench.kinds import train, train_afmoe
from tests.chipbench import rules

ROOT = harness.ROOT
CELL = "trinitymini-lora-train"
CONFIG = harness.load_json(ROOT, "chipbench", "configs", "trinity-mini.json")
JOB = harness.load_json(
    ROOT, "chipbench", "traffic", "lora-train-swa-1x8192.json")
TINY = {"hidden_size": 48, "vocab_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 12,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_experts": 16, "num_experts_per_tok": 3}
PER_LAYER = [
    "mfu_pct.train_afmoe", "swa_flash_ms.train_afmoe",
    "swa_flash_roofline.train_afmoe", "full_flash_ms.train_afmoe",
    "full_flash_roofline.train_afmoe", "attn_gate_norm_ms.train_afmoe",
    "swa_tiles_walked_pct.train_afmoe", "moe_experts_ms.train_afmoe",
    "moe_experts_roofline.train_afmoe", "moe_dispatch_ms.train_afmoe",
    "expert_load_max_over_mean.train_afmoe"]


def test_train_afmoe_kind_checks_and_measures_at_a_tiny_size():
    """``train_job``'s steps but the gang: build, step, window, the
    reference check with the picks and the load."""
    from sparkdl_tpu.parallel.train import global_batch

    hf = {**CONFIG, **TINY}
    job = {**JOB, "batch": 2, "seq": 48, "attention": "reference",
           "check": {"loss_rtol": 5e-3, "grad_norm_rtol": 5e-2}}
    cfg, params, mask, loss_fn, opt, step = train_afmoe.setup(
        hf, job, seed=2**31 + 5)
    assert cfg.pattern == "SDSDSGFGSGSG" and cfg.remat
    assert cfg.post_norm and cfg.scale_embedding
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.top_k) == (
        16, (0, 16), 3)
    assert cfg.routed_scale == 2.826 and cfg.rope_theta == 10000
    moe = params["layer_5"]["moe"]
    assert moe["w_gate_up"].dtype == jnp.bfloat16
    assert moe["w_gate_up"].shape == (16, 48, 64)
    assert moe["router"]["kernel"].shape == (48, 16)
    assert params["layer_1"]["mlp"]["gate_proj"]["kernel"].shape == (48, 96)
    attn = params["layer_0"]["attn"]
    assert attn["q_proj"]["lora_a"].dtype == jnp.float32
    assert attn["k_proj"]["kernel"].shape == (48, 2 * 16)
    # the gate is a projection of the attention mixer and takes no adapter
    assert sorted(attn["gate_proj"]) == ["kernel"]
    assert sorted(params["layer_1"]["mlp"]["gate_proj"]) == ["kernel"]
    batch = jax.tree.map(jnp.asarray, global_batch(
        np.random.default_rng(0), cfg.vocab_size, 2, 48))
    step = jax.jit(step)
    state = step(params, opt.init(params), batch)[:2]        # compiles
    state, losses, elapsed = train.measure(
        step, state, [batch], lambda seconds, steps: steps >= 5)
    assert len(losses) == 5 and elapsed > 0 and losses[-1] < losses[0]
    check = train_afmoe.reference_check(
        hf, job, cfg, state[0], batch, loss_fn, mask)
    assert check["ok"], check
    assert sorted(check["picks_differ_share"]) == [5, 7, 9, 11]
    assert all(0 <= s < 0.5 for s in check["picks_differ_share"].values())
    assert check["rows_here"] == [48 * 3] * 4        # every pair lands here
    assert check["expert_load_max_over_mean"] >= 1.0
    tight = {**job, "check": {"loss_rtol": 1e-9, "grad_norm_rtol": 1e-9}}
    assert not train_afmoe.reference_check(
        hf, tight, cfg, state[0], batch, loss_fn, mask)["ok"]


def test_the_configuration_keeps_the_published_widths():
    from sparkdl_tpu.models import HybridConfig

    cfg = HybridConfig.from_published(CONFIG)
    # S S | S F S S: both dense layers and one whole period after them
    assert cfg.pattern == "SDSDSGFGSGSG"
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.vocab_size, cfg.sliding_window) == (
                2048, 32, 4, 128, 200192, 2048)
    assert cfg.n_heads * cfg.head_dim == 2 * cfg.d_model
    assert (cfg.dense_d_ff, cfg.expert_d_ff, cfg.shared_d_ff, cfg.top_k,
            cfg.routed_scale, cfg.rope_theta, cfg.rms_eps) == (
                6144, 1024, 1024, 8, 2.826, 10000, 1e-5)
    assert cfg.post_norm and cfg.scale_embedding
    # every expert is held, and nothing but depth is cut
    assert (cfg.n_routed_experts, cfg.experts_held) == (128, (0, 128))
    assert sorted(CONFIG["reduced"]) == ["layer_types", "num_hidden_layers"]
    published = CONFIG["reduced"]["layer_types"]["from"]
    assert len(published) == 32 and published[:6] == CONFIG["layer_types"]
    assert published == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    assert flops_afmoe.attention_layers(CONFIG) == (5, 1)
    assert flops_afmoe.layers(CONFIG) == (2, 4)
    assert JOB["lora_targets"] == ["q_proj", "k_proj", "v_proj", "o_proj"]
    # 8192 x 8 / 128: the deployment's rows an expert
    assert JOB["batch"] * JOB["seq"] * cfg.top_k / cfg.n_routed_experts == 512
    # the catalog's numbers, under the catalog's keys
    for key, value in {
            "global_attn_every_n_layers": 4, "num_dense_layers": 2,
            "num_shared_experts": 1, "route_scale": 2.826,
            "route_norm": True, "score_func": "sigmoid",
            "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
            "mup_enabled": True, "tie_word_embeddings": False}.items():
        assert CONFIG[key] == value, key
    assert {"output_gate", "qk_norm", "rope_on_window_layers_only",
            "sandwich_norms", "embedding_scale"} <= set(CONFIG["assumed"])


def test_the_cell_keeps_every_rule_and_the_contracts_text_limits():
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    assert rules.refusals(bench, ROOT) == {}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "trinity-mini", "lora-train-swa-1x8192")
    for check in (rules.cell_resolves, rules.cell_reports_mfu):
        check(cell, bench, ROOT)
    for check in rules.CONFIGURATION:
        check(config, bench, ROOT)
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == PER_LAYER
    for entry in mine:
        for check in rules.PER_LAYER:
            check(entry, bench, ROOT)
        rules.reader_reads_none(entry["name"], ROOT)
    for entry in [config, cell] + mine:
        for key in ("why", "source", "layer"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200, (entry["name"], key, len(text))
            assert text.isascii() and text.isprintable(), (entry["name"], key)
    rate = next(m for m in bench["end_to_end"]
                if m["name"] == "train_tokens_per_s_per_chip")
    assert rate["workloads"][-1] == CELL


def test_flops_afmoe_against_hand_counts():
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    assert flops_afmoe.attention_matmul_params(CONFIG) == attention == (
        27_262_976)
    assert flops_afmoe.dense_mlp_params(CONFIG) == 3 * 2048 * 6144
    expert = 3 * 2048 * 1024
    assert flops_afmoe.expert_params(CONFIG) == expert == 6_291_456
    assert flops_afmoe.moe_matmul_params(CONFIG) == 2048 * 128 + 9 * expert
    # what the stage holds: 4.31 B parameters, 8.61 GB in bf16
    held = flops_afmoe.matrix_params(CONFIG)
    assert held == (
        6 * attention + 2 * 3 * 2048 * 6144
        + 4 * (2048 * 128 + 129 * expert) + 2 * 200192 * 2048
    ) == 4_306_501_632
    assert flops_afmoe.model_params(CONFIG) == held + (
        6 * (4 * 2048 + 2 * 128) + 4 * 128 + 2048)
    # a window layer sees the pairs with 0 <= i - j < 2048
    pairs = flops_afmoe.visible_pairs(8192, 2048)
    assert pairs == sum(min(i + 1, 2048) for i in range(8192)) == 14_681_088
    assert flops_afmoe.visible_pairs(8192) == 8192 * 8193 // 2 == 33_558_528
    assert flops_afmoe.visible_pairs(8192, 8192) == 33_558_528
    assert flops_afmoe.visible_pairs(8192, 1) == 8192
    window = flops_afmoe.attention_flops_per_token(
        CONFIG, 8192, window=2048, backward=True)
    assert window == 6 * 2 * 32 * 128 * pairs / 8192
    # a full layer's is flops.py's count
    full = flops_afmoe.attention_flops_per_token(
        CONFIG, 8192, window=None, backward=True)
    assert full == flops.attention_flops_per_token(CONFIG, 8192, backward=True)
    adapters = flops_afmoe.lora_adapter_params(CONFIG, 8, JOB["lora_targets"])
    assert adapters == 8 * (2048 + 4096 + 2 * (2048 + 512) + 4096 + 2048) == (
        139_264)
    assert flops_afmoe.lora_adapter_params(
        CONFIG, 8, ["gate_proj"]) == 8 * (2048 + 4096)
    want = (4 * (6 * attention + 2 * 3 * 2048 * 6144
                 + 4 * (2048 * 128 + 9 * expert) + 200192 * 2048)
            + 6 * 6 * adapters + 5 * window + full)
    assert flops_afmoe.lora_train_flops_per_token(
        CONFIG, 8192, rank=8, targets=JOB["lora_targets"]) == want
    assert 4.1e9 < want < 4.2e9
    # 8192 tokens x 8 picks: the rows of an expert layer
    assert JOB["batch"] * JOB["seq"] * CONFIG["num_experts_per_tok"] == 65_536


def test_the_tree_the_kind_builds_holds_what_is_counted():
    """``jax.eval_shape`` of the kind's seeded tree at the published
    widths: the count's parameters and the adapters."""
    from chipbench.kinds.train_hybrid import init_params
    from sparkdl_tpu.models import HybridConfig

    cfg = HybridConfig.from_published(
        CONFIG, lora_rank=JOB["lora_rank"],
        lora_targets=tuple(JOB["lora_targets"]))
    shapes = jax.eval_shape(lambda: init_params(cfg, 0))
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    size = lambda keep: sum(
        int(np.prod(x.shape)) for p, x in leaves
        if keep(jax.tree_util.keystr(p)))
    adapters = 6 * flops_afmoe.lora_adapter_params(
        CONFIG, 8, JOB["lora_targets"])
    assert size(lambda p: "lora_" in p) == adapters == 835_584
    assert size(lambda p: "lora_" not in p) == flops_afmoe.model_params(CONFIG)
    assert all(x.dtype == (jnp.float32 if "lora_" in jax.tree_util.keystr(p)
                           else jnp.bfloat16) for p, x in leaves)


def test_kernel_costs_and_their_bounds():
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")["TPU v5 lite"]
    ops, nbytes = flops_afmoe.window_attention_cost(
        CONFIG, 1, 8192, backward=False)
    assert ops == 2 * 2 * 32 * 128 * 14_681_088
    assert nbytes == 8192 * 128 * 2 * (32 + 4) * 2
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "compute"
    assert flops_afmoe.window_attention_cost(
        CONFIG, 1, 8192, backward=True) == (2 * ops, 2 * nbytes)
    full, _ = flops_afmoe.flash_attention_cost(CONFIG, 1, 8192, backward=False)
    assert full == 2 * 2 * 32 * 128 * 33_558_528
    assert ops / full == pytest.approx(0.4375, abs=1e-3)
    # a step: five window mixers and one full, forward and twice that back
    step = flops_afmoe.attention_step_cost(CONFIG, 1, 8192)
    assert step["window"] == (5 * 3 * ops, 5 * 3 * nbytes)
    assert step["full"] == (3 * full, 3 * nbytes)
    # a window that covers the sequence costs what a full layer costs
    assert flops_afmoe.window_attention_cost(
        {**CONFIG, "sliding_window": 8192}, 1, 8192, backward=False)[0] == full
    ops, nbytes = flops_afmoe.grouped_matmul_cost(CONFIG, 65536)
    assert ops == 65536 * 2 * 3 * 2048 * 1024
    assert nbytes == (65536 * (2048 + 2048 + 1024 + 2048) * 2
                      + 128 * 3 * 2048 * 1024 * 2)
    # 512 rows an expert: the products (4.19 ms) outlast the reading of
    # the experts' 1.61 GB and of the rows (3.11 ms)
    by = flops.roofline_seconds(ops, nbytes, peaks)
    assert by[1] == "compute" and by[0] == pytest.approx(4.19e-3, rel=0.01)
    assert flops.roofline_seconds(*flops_afmoe.grouped_matmul_cost(
        CONFIG, 16384), peaks)[1] == "memory"
    assert flops_afmoe.grouped_matmul_cost(CONFIG, 0)[0] == 0


def test_afmoe_readers_on_a_made_up_run():
    spec = harness.load_cell(CELL)
    assert spec["traffic"]["kind"] == "train_afmoe"
    assert [m["name"] for m in spec["per_layer"]] == PER_LAYER
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "train_tokens_per_s_per_chip"]
    peak = spec["peaks"]["TPU v5 lite"]["bf16_flops_per_s"]
    row = lambda s: {"forward": s, "backward": 0.0, "recompute": 0.0,
                     "total": s}
    tiles = lambda kernel, window, walked: {
        "kernel": kernel, "s": "8192", "d": "128", "bq": "512", "bk": "512",
        "chosen": "rule", "window": str(window), "tiles_walked": str(walked),
        "tiles_causal": "136", "count": 1}
    run = {"spec": spec, "device": {"kind": "TPU v5 lite"},
           "end_to_end": {"train_tokens_per_s_per_chip": 14000.0},
           "by_scope": {"sparkdl.attn": row(0.050),
                        "sparkdl.attn_window": row(0.060),
                        "sparkdl.attn_full": row(0.030),
                        "sparkdl.attn_gate": row(0.004),
                        "sparkdl.attn_qknorm": row(0.006),
                        "sparkdl.moe_experts": row(0.110),
                        "sparkdl.moe_route": row(0.010),
                        "sparkdl.moe_dispatch": row(0.070)},
           "flash_tiles": [tiles(k, w, n) for k in ("fwd", "dq", "dkv")
                           for w, n in ((2048, 70), (0, 136))],
           "check": {"expert_load_max_over_mean": 1.3}}
    read = lambda name: harness.load_reader(name)(run)
    per_token = flops_afmoe.lora_train_flops_per_token(
        spec["config"], 8192, rank=8, targets=JOB["lora_targets"])
    assert read("mfu_pct.train_afmoe") == pytest.approx(
        100 * per_token * 14000 / peak)
    assert read("swa_flash_ms.train_afmoe") == pytest.approx(60.0)
    assert read("full_flash_ms.train_afmoe") == pytest.approx(30.0)
    assert read("attn_gate_norm_ms.train_afmoe") == pytest.approx(10.0)
    assert read("moe_experts_ms.train_afmoe") == pytest.approx(110.0)
    assert read("moe_dispatch_ms.train_afmoe") == pytest.approx(80.0)
    assert read("expert_load_max_over_mean.train_afmoe") == 1.3
    assert read("swa_tiles_walked_pct.train_afmoe") == pytest.approx(
        100 * 70 / 136)
    # five window mixers over the pairs a row can see, one full mixer
    # over the causal pairs: forward and twice that backward
    need = 5 * 3 * (2 * 2 * 32 * 128 * 14_681_088) / peak
    assert read("swa_flash_roofline.train_afmoe") == pytest.approx(
        100 * need / 0.060)
    need = 3 * (2 * 2 * 32 * 128 * 33_558_528) / peak
    assert read("full_flash_roofline.train_afmoe") == pytest.approx(
        100 * need / 0.030)
    # four layers, two passes, 65,536 rows each, bound by compute
    need = 4 * 2 * (65536 * 2 * 3 * 2048 * 1024) / peak
    assert read("moe_experts_roofline.train_afmoe") == pytest.approx(
        100 * need / 0.110)
    for name in ("mfu_pct.train_afmoe", "swa_flash_roofline.train_afmoe",
                 "full_flash_roofline.train_afmoe",
                 "moe_experts_roofline.train_afmoe"):
        assert 0 < read(name) < 100
    # kernels that only mask the window: the counter says so
    run["flash_tiles"] = [tiles(k, 2048, 136) for k in ("fwd", "dq", "dkv")]
    assert read("swa_tiles_walked_pct.train_afmoe") == 100.0
    # a run of a program without the scopes or the counter (the parent
    # commit, or telemetry off): the metric is left out
    run["flash_tiles"] = [tiles("fwd", 0, 136)]
    assert read("swa_tiles_walked_pct.train_afmoe") is None
    del run["by_scope"], run["flash_tiles"]
    for name in PER_LAYER[1:-1]:
        assert read(name) is None, name


def test_a_checkout_without_the_window_refuses_the_cell(monkeypatch):
    """The parent commit under this benchmark's files: the kind says so
    and exits, before any launcher."""
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == train_afmoe.WINDOWED
        else real(name, *a))
    monkeypatch.setattr(
        "sparkdl_tpu.horovod.launcher.probe_local_devices",
        lambda *a: pytest.fail("the launcher was reached"))
    with pytest.raises(SystemExit, match="no windowed attention"):
        train_afmoe.run(harness.load_cell(CELL), seed=1, seconds=1,
                        trace=False)
