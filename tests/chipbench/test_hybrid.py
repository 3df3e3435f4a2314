"""The ``train_hybrid`` kind and what it brings (its count of required
work, its readers, its scopes) at a tiny size on the CPU: no gang, no
chip. The program's own tests are in ``tests/models/test_hybrid.py``
and ``tests/ops/test_ssd.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, flops_hybrid, hybrid_scopes, scopes
from chipbench import run as harness
from chipbench.kinds import train, train_hybrid

ROOT = harness.ROOT
CELL = "nemotron3super-lora-train"
CONFIG = harness.load_json(
    ROOT, "chipbench", "configs", "nemotron-3-super-120b-a12b.json")
TINY = {"hidden_size": 64, "vocab_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
        "mamba_head_dim": 16, "n_groups": 2, "ssm_state_size": 16,
        "chunk_size": 16, "n_routed_experts": 8, "num_experts_per_tok": 4,
        "moe_latent_size": 32, "moe_intermediate_size": 48,
        "moe_shared_expert_intermediate_size": 96,
        "hybrid_override_pattern": "ME*E", "num_hidden_layers": 4,
        "reduced": {"n_routed_experts": {"from": 32, "to": 8}}}


def test_train_hybrid_kind_checks_and_measures_at_a_tiny_size():
    """``train_job``'s steps but the gang: build, step, window, the
    reference check with the share's picks and load."""
    from sparkdl_tpu.parallel.train import global_batch

    hf = {**CONFIG, **TINY}
    job = {**harness.load_json(ROOT, "chipbench", "traffic",
                               "lora-train-1x8192.json"),
           "batch": 2, "seq": 48, "attention": "reference",
           "check": {"loss_rtol": 5e-3, "grad_norm_rtol": 5e-2}}
    cfg, params, mask, loss_fn, opt, step = train_hybrid.setup(
        hf, job, seed=2**31 + 5)
    assert (cfg.n_routed_experts, cfg.experts_held) == (32, (8, 8))
    assert cfg.pattern == "ME*E" and cfg.remat
    assert params["layer_1"]["moe"]["w_up"].dtype == jnp.bfloat16
    assert params["layer_1"]["moe"]["w_up"].shape == (8, 32, 48)
    assert params["layer_1"]["moe"]["router"]["kernel"].shape == (64, 32)
    assert params["layer_0"]["mamba"]["in_proj"]["lora_a"].dtype == jnp.float32
    batch = jax.tree.map(jnp.asarray, global_batch(
        np.random.default_rng(0), cfg.vocab_size, 2, 48))
    assert int(batch["inputs"].max()) < 256
    step = jax.jit(step)
    state = step(params, opt.init(params), batch)[:2]        # compiles
    state, losses, elapsed = train.measure(
        step, state, [batch], lambda seconds, steps: steps >= 5)
    assert len(losses) == 5 and elapsed > 0 and losses[-1] < losses[0]
    check = train_hybrid.reference_check(
        hf, job, cfg, state[0], batch, loss_fn, mask)
    assert check["ok"], check
    assert sorted(check["picks_differ_share"]) == [1, 3]
    assert all(0 <= s < 0.5 for s in check["picks_differ_share"].values())
    assert len(check["rows_here"]) == 2
    assert all(0 < rows <= 48 * 4 for rows in check["rows_here"])
    assert check["expert_load_max_over_mean"] >= 1.0
    tight = {**job, "check": {"loss_rtol": 1e-9, "grad_norm_rtol": 1e-9}}
    assert not train_hybrid.reference_check(
        hf, tight, cfg, state[0], batch, loss_fn, mask)["ok"]


def test_the_configuration_keeps_the_published_widths():
    cfg = train_hybrid.hybrid_config(CONFIG)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.conv_kernel, cfg.chunk_size) == (128, 64, 8, 128, 4, 128)
    assert (cfg.latent, cfg.expert_d_ff, cfg.shared_d_ff, cfg.top_k,
            cfg.routed_scale) == (1024, 2688, 5376, 22, 5)
    # the router keeps its width; the chip holds the second quarter
    assert (cfg.n_routed_experts, cfg.experts_held) == (512, (128, 128))
    assert cfg.pattern == "MEMEMEMEM*E" and cfg.vocab_size == 32768
    assert cfg.pattern in CONFIG["reduced"]["hybrid_override_pattern"]["from"]
    assert CONFIG["num_nextn_predict_layers"] == 0


def test_flops_hybrid_against_hand_counts():
    mamba = 4096 * (2 * 8192 + 2 * 8 * 128 + 128) + 8192 * 4096
    assert flops_hybrid.mamba_matmul_params(CONFIG) == mamba == 109_576_192
    attn = 2 * 4096 * 4096 + 2 * 4096 * 256
    assert flops_hybrid.attention_matmul_params(CONFIG) == attn
    expert = 2 * 1024 * 2688
    outside = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert flops_hybrid.moe_matmul_params(CONFIG) == (
        outside + 22 * 128 / 512 * expert)          # 5.5 picks land here
    # what the share holds: 4.65 B parameters, 9.3 GB in bf16
    held = flops_hybrid.model_params(CONFIG)
    assert held == (
        5 * (mamba + 5 * 10240 + 3 * 128 + 8192 + 4096)
        + 5 * (outside + 128 * expert + 512 + 4096) + attn + 4096
        + 2 * 32768 * 4096 + 4096) == 4_648_163_712
    # the scan at chunk 128: C B^T a group, its product with x, the
    # chunk's state and C times the state carried in, a head
    scan = 2 * 64.5 * 128 * 8 + 128 * (2 * 64.5 * 64 + 4 * 64 * 128)
    assert flops_hybrid.scan_flops_per_token(CONFIG) == scan
    targets = ["in_proj", "out_proj", "q_proj", "v_proj"]
    adapters = flops_hybrid.lora_adapter_params(CONFIG, 8, targets)
    assert adapters == {
        "M": 8 * (4096 + 18560) + 8 * (8192 + 4096),
        "*": 8 * (4096 + 4096) + 8 * (4096 + 256), "E": 0}
    want = (4 * (5 * mamba + 5 * flops_hybrid.moe_matmul_params(CONFIG)
                 + attn + 32768 * 4096 + 5 * 4 * 10240)
            + 6 * (5 * adapters["M"] + adapters["*"])
            + 6 * 2 * 32 * 128 * 8193 / 2 + 5 * 3 * scan)
    assert flops_hybrid.lora_train_flops_per_token(
        CONFIG, 8192, rank=8, targets=targets) == want
    assert 4.5e9 < want < 5.0e9
    # with its adapters, the tree the kind builds (jax.eval_shape of it)
    assert held + 5 * adapters["M"] + adapters["*"] == 4_649_661_824


def test_kernel_costs_and_their_bounds():
    peaks = harness.load_json(ROOT, "chipbench", "peaks.json")["TPU v5 lite"]
    ops, nbytes = flops_hybrid.ssd_scan_cost(CONFIG, 1, 8192, backward=False)
    assert ops == 8192 * flops_hybrid.scan_flops_per_token(CONFIG)
    assert nbytes == 8192 * (2 * 8192 * 2 + 2 * 1024 * 2 + 128 * 4)
    assert flops.roofline_seconds(ops, nbytes, peaks)[1] == "memory"
    back = flops_hybrid.ssd_scan_cost(CONFIG, 1, 8192, backward=True)
    assert back[0] == 2 * ops and back[1] > nbytes
    ops, nbytes = flops_hybrid.grouped_matmul_cost(CONFIG, 45056)
    assert ops == 45056 * 2 * 2 * 1024 * 2688
    assert nbytes == 45056 * 2 * (1024 + 2688) * 2 + 128 * 2 * 1024 * 2688 * 2
    # 352 rows an expert: reading the experts' 1.4 GB takes as long as
    # the products (2.54 ms against 2.52 ms); four times the rows would
    # be bound by compute
    by = flops.roofline_seconds(ops, nbytes, peaks)
    assert by[1] == "memory" and by[0] == pytest.approx(2.54e-3, rel=0.01)
    assert flops.roofline_seconds(*flops_hybrid.grouped_matmul_cost(
        CONFIG, 4 * 45056), peaks)[1] == "compute"
    # a layer that received nothing still reads nothing of its rows
    assert flops_hybrid.grouped_matmul_cost(CONFIG, 0)[0] == 0


def test_dotted_scopes_are_kept_apart():
    stack = ("jit(step)/transpose(jvp(HybridDecoder))/layer_1/sparkdl.moe/"
             "moe/sparkdl.moe.dispatch/gather")
    assert scopes.scope_of(stack)[0] == "sparkdl.moe"       # as accepted
    assert scopes.scope_of(hybrid_scopes.flatten(stack)) == (
        "sparkdl.moe_dispatch", "backward")
    assert hybrid_scopes.flatten("a/sparkdl.ssm/b") == "a/sparkdl.ssm/b"
    table = scopes.by_scope([
        (2e9, hybrid_scopes.flatten("x/sparkdl.ssm/sparkdl.ssm.scan/dot")),
        (1e9, hybrid_scopes.flatten("x/rematted_computation/sparkdl.ssm/"
                                    "sparkdl.ssm.scan/dot")),
        (4e9, hybrid_scopes.flatten("x/sparkdl.ssm/dot"))], steps=2)
    run = {"by_scope": table}
    assert hybrid_scopes.step_seconds(run, "sparkdl.ssm.scan") == 1.5
    assert hybrid_scopes.step_seconds(run, "sparkdl.ssm") == 2.0
    assert hybrid_scopes.step_seconds(run, "sparkdl.moe.route") is None
    assert hybrid_scopes.step_seconds({}, "sparkdl.ssm.scan") is None


def test_hybrid_readers_on_a_made_up_run():
    spec = harness.load_cell(CELL)
    assert [m["name"] for m in spec["per_layer"]] == [
        "mfu_pct.train_hybrid", "ssd_scan_ms.train_hybrid",
        "ssd_scan_roofline.train_hybrid", "moe_experts_ms.train_hybrid",
        "moe_experts_roofline.train_hybrid", "moe_dispatch_ms.train_hybrid",
        "expert_load_max_over_mean.train_hybrid"]
    peaks = spec["peaks"]["TPU v5 lite"]
    row = lambda s: {"forward": s, "backward": 0.0, "recompute": 0.0,
                     "total": s}
    run = {"spec": spec, "device": {"kind": "TPU v5 lite"},
           "end_to_end": {"train_tokens_per_s_per_chip": 12000.0},
           "by_scope": {"sparkdl.ssm_scan": row(0.040),
                        "sparkdl.moe_experts": row(0.050),
                        "sparkdl.moe_route": row(0.004),
                        "sparkdl.moe_dispatch": row(0.006)},
           "traced_rows": [[45000] * 5, [45112] * 5],
           "check": {"expert_load_max_over_mean": 1.25}}
    read = lambda name: harness.load_reader(name)(run)
    per_token = flops_hybrid.lora_train_flops_per_token(
        spec["config"], 8192, rank=8,
        targets=["in_proj", "out_proj", "q_proj", "v_proj"])
    assert read("mfu_pct.train_hybrid") == pytest.approx(
        100 * per_token * 12000 / 197e12)
    assert read("ssd_scan_ms.train_hybrid") == pytest.approx(40.0)
    assert read("moe_experts_ms.train_hybrid") == pytest.approx(50.0)
    assert read("moe_dispatch_ms.train_hybrid") == pytest.approx(10.0)
    assert read("expert_load_max_over_mean.train_hybrid") == 1.25
    fwd = flops_hybrid.ssd_scan_cost(spec["config"], 1, 8192, backward=False)
    bwd = flops_hybrid.ssd_scan_cost(spec["config"], 1, 8192, backward=True)
    need = 5 * (fwd[1] + bwd[1]) / peaks["hbm_bytes_per_s"]   # by memory
    assert read("ssd_scan_roofline.train_hybrid") == pytest.approx(
        100 * need / 0.040)
    nbytes = sum(flops_hybrid.grouped_matmul_cost(spec["config"], r)[1]
                 for r in (45000, 45112)) / 2
    need = 5 * 2 * nbytes / peaks["hbm_bytes_per_s"]          # by memory
    assert read("moe_experts_roofline.train_hybrid") == pytest.approx(
        100 * need / 0.050)
    assert 0 < read("moe_experts_roofline.train_hybrid") < 100
    # a run of the parent commit has no such scope: the metric is left out
    del run["by_scope"]
    assert read("ssd_scan_roofline.train_hybrid") is None
    assert read("moe_experts_roofline.train_hybrid") is None
    assert read("moe_dispatch_ms.train_hybrid") is None
