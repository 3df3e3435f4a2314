"""The ``train_mla`` kind and what it brings (its count of required
work, its readers, its configuration) at a tiny size on the CPU: no
gang, no chip. The program's own tests are in
``tests/models/test_mla.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, flops_mla
from chipbench import run as harness
from chipbench.kinds import train, train_mla

ROOT = harness.ROOT
CELL = "glm47flash-lora-train"
CONFIG = harness.load_json(ROOT, "chipbench", "configs", "glm-4.7-flash.json")
JOB = harness.load_json(
    ROOT, "chipbench", "traffic", "lora-train-mla-1x8192.json")
TINY = {"hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
        "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32,
        "intermediate_size": 128, "moe_intermediate_size": 48,
        "n_routed_experts": 16, "num_hidden_layers": 3}


def test_train_mla_kind_checks_and_measures_at_a_tiny_size():
    """``train_job``'s steps but the gang: build, step, window, the
    reference check with the picks and the load."""
    from sparkdl_tpu.parallel.train import global_batch

    hf = {**CONFIG, **TINY}
    job = {**JOB, "batch": 2, "seq": 48, "attention": "reference",
           "check": {"loss_rtol": 5e-3, "grad_norm_rtol": 5e-2}}
    cfg, params, mask, loss_fn, opt, step = train_mla.setup(
        hf, job, seed=2**31 + 5)
    assert cfg.pattern == "LDLGLG" and cfg.remat
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.top_k) == (
        16, (0, 16), 4)
    assert cfg.routed_scale == 1.8 and cfg.rope_theta == 1000000
    moe = params["layer_3"]["moe"]
    assert moe["w_gate_up"].dtype == jnp.bfloat16
    assert moe["w_gate_up"].shape == (16, 64, 96)
    assert moe["router"]["kernel"].shape == (64, 16)
    assert params["layer_1"]["mlp"]["gate_proj"]["kernel"].shape == (64, 128)
    assert params["layer_0"]["mla"]["q_a_proj"]["lora_a"].dtype == jnp.float32
    assert params["layer_4"]["mla"]["kv_b_proj"]["kernel"].shape == (
        32, 4 * (24 + 32))
    batch = jax.tree.map(jnp.asarray, global_batch(
        np.random.default_rng(0), cfg.vocab_size, 2, 48))
    step = jax.jit(step)
    state = step(params, opt.init(params), batch)[:2]        # compiles
    state, losses, elapsed = train.measure(
        step, state, [batch], lambda seconds, steps: steps >= 5)
    assert len(losses) == 5 and elapsed > 0 and losses[-1] < losses[0]
    check = train_mla.reference_check(
        hf, job, cfg, state[0], batch, loss_fn, mask)
    assert check["ok"], check
    assert sorted(check["picks_differ_share"]) == [3, 5]
    assert all(0 <= s < 0.5 for s in check["picks_differ_share"].values())
    assert check["rows_here"] == [48 * 4] * 2        # every pair lands here
    assert check["expert_load_max_over_mean"] >= 1.0
    tight = {**job, "check": {"loss_rtol": 1e-9, "grad_norm_rtol": 1e-9}}
    assert not train_mla.reference_check(
        hf, tight, cfg, state[0], batch, loss_fn, mask)["ok"]


def test_the_configuration_keeps_the_published_widths():
    from sparkdl_tpu.models import HybridConfig

    cfg = HybridConfig.from_published(CONFIG)
    assert cfg.pattern == "LD" + "LG" * 6
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.vocab_size) == (
        2048, 20, 20, 154880)
    assert (cfg.q_rank, cfg.kv_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_dim) == (768, 512, 192, 64, 256)
    assert (cfg.dense_d_ff, cfg.expert_d_ff, cfg.shared_d_ff, cfg.top_k,
            cfg.routed_scale, cfg.rope_theta, cfg.rms_eps) == (
                10240, 1536, 1536, 4, 1.8, 1000000, 1e-5)
    # every expert is held, and nothing but depth is cut
    assert (cfg.n_routed_experts, cfg.experts_held) == (64, (0, 64))
    assert sorted(CONFIG["reduced"]) == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert CONFIG["num_nextn_predict_layers"] == 0
    assert (CONFIG["n_group"], CONFIG["topk_group"]) == (1, 1)
    assert JOB["lora_targets"] == list(flops_mla.mla_projections(CONFIG))
    # 8192 x 4 / 64: the deployment's rows an expert
    assert JOB["batch"] * JOB["seq"] * cfg.top_k / cfg.n_routed_experts == 512


def test_the_entries_keep_the_contracts_text_limits():
    # rules.py holds a cell's ``why`` to 200 characters but not a
    # configuration's, and the driver refused this PR once for 204
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    entries = ([c for c in bench["configs"] if c["name"] == CONFIG["name"]]
               + [w for w in bench["workloads"] if w["name"] == CELL]
               + [m for m in bench["per_layer"] if m.get("workloads") == [CELL]])
    assert len(entries) == 10
    for entry in entries:
        for key in ("why", "source", "layer"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200, (entry["name"], key, len(text))
            assert text.isascii() and text.isprintable(), (entry["name"], key)


def test_flops_mla_against_hand_counts():
    mla = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
           + 20 * 256 * 2048)
    assert flops_mla.mla_matmul_params(CONFIG) == mla == 21_757_952
    assert flops_mla.dense_mlp_params(CONFIG) == 3 * 2048 * 10240
    expert = 3 * 2048 * 1536
    assert flops_mla.expert_params(CONFIG) == expert == 9_437_184
    assert flops_mla.moe_matmul_params(CONFIG) == 2048 * 64 + 5 * expert
    # what the stage holds: 4.53 B parameters, 9.06 GB in bf16
    held = flops_mla.model_params(CONFIG)
    assert held == (
        7 * (mla + 768 + 512 + 2 * 2048) + 3 * 2048 * 10240
        + 6 * (2048 * 64 + 64 + 65 * expert) + 2 * 154880 * 2048 + 2048
    ) == 4_530_936_960
    # the expanded form at 256 for scores and values is flops.py's count
    attention = flops_mla.attention_flops_per_token(
        CONFIG, 8192, backward=True)
    assert attention == flops.attention_flops_per_token(
        {**CONFIG, "head_dim": 256}, 8192, backward=True)
    assert attention == 6 * 2 * 20 * 256 * 8193 / 2
    adapters = flops_mla.lora_adapter_params(CONFIG, 8, JOB["lora_targets"])
    assert adapters == 8 * (2048 + 768 + 768 + 5120 + 2048 + 576 + 512
                            + 8960 + 5120 + 2048) == 223_744
    assert flops_mla.lora_adapter_params(CONFIG, 8, ["o_proj"]) == 8 * 7168
    want = (4 * (7 * mla + 3 * 2048 * 10240 + 6 * (2048 * 64 + 5 * expert)
                 + 154880 * 2048) + 6 * 7 * adapters + 7 * attention)
    assert flops_mla.lora_train_flops_per_token(
        CONFIG, 8192, rank=8, targets=JOB["lora_targets"]) == want
    assert 5.0e9 < want < 5.1e9
    # with its adapters, the tree the kind builds (jax.eval_shape of it)
    assert held + 7 * adapters == 4_532_503_168


def test_kernel_costs_and_their_bounds():
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")["TPU v5 lite"]
    ops, nbytes = flops_mla.flash_attention_cost(CONFIG, 1, 8192, backward=False)
    assert ops == 8192 * 2 * 20 * 512 * 8193 / 2
    assert nbytes == 8192 * 20 * 4 * 256 * 2
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "compute"
    back = flops_mla.flash_attention_cost(CONFIG, 1, 8192, backward=True)
    assert back == (2 * ops, 2 * nbytes)
    ops, nbytes = flops_mla.grouped_matmul_cost(CONFIG, 32768)
    assert ops == 32768 * 2 * 3 * 2048 * 1536
    assert nbytes == (32768 * (2048 + 3072 + 1536 + 2048) * 2
                      + 64 * 3 * 2048 * 1536 * 2)
    # 512 rows an expert: the products (3.14 ms) outlast the reading of
    # the experts' 1.21 GB and of the rows (2.17 ms); a quarter of the
    # rows would be bound by memory
    by = flops.roofline_seconds(ops, nbytes, peaks)
    assert by[1] == "compute" and by[0] == pytest.approx(3.14e-3, rel=0.01)
    assert flops.roofline_seconds(*flops_mla.grouped_matmul_cost(
        CONFIG, 8192), peaks)[1] == "memory"
    assert flops_mla.grouped_matmul_cost(CONFIG, 0)[0] == 0


def test_mla_readers_on_a_made_up_run():
    spec = harness.load_cell(CELL)
    assert spec["traffic"]["kind"] == "train_mla"
    assert [m["name"] for m in spec["per_layer"]] == [
        "mfu_pct.train_mla", "mla_flash_ms.train_mla",
        "mla_flash_roofline.train_mla", "mla_latent_ms.train_mla",
        "moe_experts_ms.train_mla", "moe_experts_roofline.train_mla",
        "moe_dispatch_ms.train_mla", "expert_load_max_over_mean.train_mla"]
    assert [m["name"] for m in spec["end_to_end"]] == [
        "setup_s", "train_tokens_per_s_per_chip"]
    peak = spec["peaks"]["TPU v5 lite"]["bf16_flops_per_s"]
    row = lambda s: {"forward": s, "backward": 0.0, "recompute": 0.0,
                     "total": s}
    run = {"spec": spec, "device": {"kind": "TPU v5 lite"},
           "end_to_end": {"train_tokens_per_s_per_chip": 11000.0},
           "trace": {"ops_s": {
               "%sparkdl_flash_fwd.3 = bf16[1,20,8192,256]": 0.300,
               "%sparkdl_flash_dq.1 = bf16[1,20,8192,256]": 0.150,
               "%sparkdl_flash_dkv.2 = (bf16[1,20,8192,256])": 0.210,
               "%fusion.12 = bf16[8192,2048]": 0.500}},
           "by_scope": {"sparkdl.mla_latent": row(0.080),
                        "sparkdl.mla_core": row(0.220),
                        "sparkdl.moe_experts": row(0.120),
                        "sparkdl.moe_route": row(0.010),
                        "sparkdl.moe_dispatch": row(0.050)},
           "check": {"expert_load_max_over_mean": 1.3}}
    read = lambda name: harness.load_reader(name)(run)
    per_token = flops_mla.lora_train_flops_per_token(
        spec["config"], 8192, rank=8, targets=JOB["lora_targets"])
    assert read("mfu_pct.train_mla") == pytest.approx(
        100 * per_token * 11000 / peak)
    assert read("mla_flash_ms.train_mla") == pytest.approx(220.0)
    assert read("mla_latent_ms.train_mla") == pytest.approx(80.0)
    assert read("moe_experts_ms.train_mla") == pytest.approx(120.0)
    assert read("moe_dispatch_ms.train_mla") == pytest.approx(60.0)
    assert read("expert_load_max_over_mean.train_mla") == 1.3
    # seven mixers, forward and twice that backward, bound by compute
    need = 7 * 3 * (8192 * 2 * 20 * 512 * 8193 / 2) / peak
    assert read("mla_flash_roofline.train_mla") == pytest.approx(
        100 * need / 0.220)
    # six layers, two passes, 32,768 rows each, bound by compute
    need = 6 * 2 * (32768 * 2 * 3 * 2048 * 1536) / peak
    assert read("moe_experts_roofline.train_mla") == pytest.approx(
        100 * need / 0.120)
    for name in ("mfu_pct.train_mla", "mla_flash_roofline.train_mla",
                 "moe_experts_roofline.train_mla"):
        assert 0 < read(name) < 100
    # a run of a program without the scopes or the kernels' names (the
    # parent commit): the metric is left out
    del run["by_scope"], run["trace"]
    for name in ("mla_flash_ms.train_mla", "mla_flash_roofline.train_mla",
                 "mla_latent_ms.train_mla", "moe_experts_ms.train_mla",
                 "moe_experts_roofline.train_mla",
                 "moe_dispatch_ms.train_mla"):
        assert read(name) is None


def test_a_checkout_without_latent_attention_refuses_the_cell(monkeypatch):
    """The parent commit under this benchmark's files: the kind says so
    and exits, before any launcher."""
    import importlib.util

    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "sparkdl_tpu.models.mla"
        else real(name, *a))
    with pytest.raises(SystemExit, match="no latent attention"):
        train_mla.run(harness.load_cell(CELL), seed=1, seconds=1, trace=False)
