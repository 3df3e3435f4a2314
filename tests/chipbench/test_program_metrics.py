"""The per-layer metrics that read what the PROGRAM names (PR 24): its
launch record's spans and its kernels' names in the device trace, on
made-up runs; and the by-scope grouping of ``chipbench.scopes`` on a
made-up event list. No gang, no chip."""

import pytest

from chipbench import flash_kernels, flops, launch_spans, scopes
from chipbench import run as harness

CELL = "mistral7b-lora-train"
NEW = ("slot_probe_s.train", "worker_boot_s.train", "hvd_init_s.train",
       "xla_compile_s.train", "flash_attn_ms.train",
       "flash_attn_roofline.train")
FWD = ("%sparkdl_flash_fwd.{} = (bf16[4,32,2048,128]{{3,2,1,0:T(8,128)(2,1)}}, "
       "f32[4,32,2048,1]{{3,2,1,0}}) custom-call(bf16[4,32,2048,128] %x)")
DQ = "%sparkdl_flash_dq.{} = bf16[4,32,2048,128]{{3,2,1,0}} custom-call(%q)"
DKV = ("%sparkdl_flash_dkv.{} = (bf16[4,32,2048,128]{{3,2,1,0}}, "
       "bf16[4,32,2048,128]{{3,2,1,0}}) custom-call(%q)")


def span(name, start, end, rank=None, **args):
    return {"name": name, "start": start, "end": end, "rank": rank,
            "cause": None, "launch_id": "1-0", "args": args}


def made_up_run():
    """A run as ``kinds.train.summarize`` returns it, started at
    t=1000 with a window that opens at t=1052."""
    spec = harness.load_cell(CELL)
    spec["started"] = 1000.0
    ops_s = {"%fusion.3299 = f32[4,256,32768]{2,1,0} fusion(%a)": 0.105,
             "%while.5 = (s32[], f32[]) while(%t)": 0.246}
    for i in range(32):
        ops_s[FWD.format(i)] = 0.0120           # 16 layers + the remat's 16
    for i in range(16):
        ops_s[DQ.format(i)] = 0.0180
        ops_s[DKV.format(i)] = 0.0243
    return {
        "spec": spec, "device": {"kind": "TPU v5 lite"}, "chips": 1,
        "end_to_end": {"setup_s": 52.0, "train_tokens_per_s_per_chip": 5444.0},
        "launch_s": 18.0, "compile_s": 12.4,
        "trace": {"busy_s": 4.49, "window_s": 4.5, "ops_s": ops_s},
        "launch_spans": [
            span("gang.slot_probe", 1000.5, 1009.0, cached=False),
            span("gang.slot_probe", 1009.1, 1009.1, cached=True),
            span("gang.slot_probe", 1009.2, 1009.2, cached=True),
            span("gang.slot_claim", 1009.1, 1009.15),
            span("gang.spawn", 1009.3, 1009.35),
            span("worker.boot", 1009.32, 1016.0, rank=0),
            span("worker.connect", 1016.0, 1016.1, rank=0),
            span("hvd.init", 1016.1, 1016.35, rank=0),
            span("gang.rendezvous", 1009.4, 1016.5),
            span("gang.ready", 1016.5, 1016.5),
            span("worker.job", 1018.0, 1100.0, rank=0),
            span("xla.compile", 1028.0, 1030.5, rank=0, program="jit(init)"),
            span("xla.compile", 1031.0, 1043.5, rank=0, program="jit(step)"),
            span("xla.compile", 1031.0, 1043.0, rank=1, program="jit(step)"),
            # the reference check compiles after the window: not set-up
            span("xla.compile", 1090.0, 1095.0, rank=0, program="jit(f)"),
        ]}


def read(name, run):
    return harness.load_reader(name)(run)


def test_the_new_metrics_are_entries_of_the_cell_and_nothing_else_moved():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:4] == ["launch_s.train", "compile_s.train", "mfu_pct.train",
                         "device_idle_pct.train"]
    assert tuple(names[4:]) == NEW
    layers = {m["layer"] for m in bench["per_layer"][:4]} | {"kernels"}
    for m in bench["per_layer"][4:]:
        assert m["workloads"] == [CELL] and m["layer"] in layers
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert [m["name"] for m in harness.load_cell(CELL)["per_layer"]] == names


def test_launch_readers_on_a_made_up_run():
    run = made_up_run()
    assert read("slot_probe_s.train", run) == pytest.approx(8.5)
    # gang.spawn start to the slowest rank's worker.boot end
    assert read("worker_boot_s.train", run) == pytest.approx(1016.0 - 1009.3)
    assert read("hvd_init_s.train", run) == pytest.approx(0.25)
    # rank 0's, ended before t = 1000 + 52: init 2.5 s + step 12.5 s
    assert read("xla_compile_s.train", run) == pytest.approx(15.0)
    # the three add up to the harness's own launch_s within 2 s
    parts = sum(read(n, run) for n in NEW[:3])
    assert abs(parts - run["launch_s"]) < 2.6
    gang = dict(run, launch_spans=run["launch_spans"] + [
        span("worker.boot", 1009.34, 1017.5, rank=1),
        span("hvd.init", 1017.6, 1020.6, rank=1)])
    assert read("worker_boot_s.train", gang) == pytest.approx(8.2)
    assert read("hvd_init_s.train", gang) == pytest.approx(3.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_returns_none_where_there_is_nothing_to_read(name):
    """The parent commit keeps no launch record and names no kernel; a
    run without a trace has no device events: the metric is left out of
    the line, nothing raises."""
    run = made_up_run()
    assert read(name, {}) is None
    bare = {k: v for k, v in run.items()
            if k not in ("trace", "launch_spans", "launch_s")}
    assert read(name, bare) is None
    assert read(name, dict(run, launch_spans=[], trace=None)) is None
    unnamed = dict(run, launch_spans=None, trace={
        "busy_s": 1.0, "window_s": 1.0,
        "ops_s": {"%attn.94 = (bf16[4,32,2048,128]) custom-call(%q)": 0.02}})
    assert read(name, unnamed) is None


def test_launch_spans_come_from_the_programs_record_after_a_launch():
    from sparkdl_tpu import observe

    observe._reset_for_tests()
    try:
        assert launch_spans.of({"launch_s": 1.0}) is None   # nothing recorded
        observe.complete("gang.slot_probe", 5.0, 8.0, cat="launch",
                         cached=False)
        (probe,) = launch_spans.of({"launch_s": 1.0})
        assert probe["name"] == "gang.slot_probe" and probe["rank"] is None
        assert read("slot_probe_s.train", {"launch_s": 1.0}) == 8.0
        assert launch_spans.of({}) is None                  # no launch ran
    finally:
        observe._reset_for_tests()


def test_flash_kernel_time_and_roofline_by_hand():
    run = made_up_run()
    found = flash_kernels.seconds(run["trace"]["ops_s"])
    assert found == pytest.approx(
        {"fwd": 32 * 0.0120, "dq": 16 * 0.0180, "dkv": 16 * 0.0243})
    # three traced steps: (0.384 + 0.288 + 0.3888) s / 3 = 353.6 ms a step
    ms = read("flash_attn_ms.train", run)
    assert ms == pytest.approx(1e3 * 1.0608 / 3)
    assert ms * 3 == pytest.approx(1e3 * sum(found.values()))
    # required work of one step, by hand: 16 layers x 8192 tokens x
    # 6 matmuls x 2 x 32 heads x 128 x (2048 + 1) / 2 operations; the
    # remat's second forward is not in it. Compute-bound: 6.6e12 / 197e12.
    ops = 16 * 8192 * 6 * 2 * 32 * 128 * (2048 + 1) / 2
    assert ops == pytest.approx(6.6e12, rel=0.01)
    tensor = 4 * 2048 * 32 * 128 * 2
    assert 16 * 12 * tensor / 819e9 < ops / 197e12
    want = 100 * (ops / 197e12) / (1.0608 / 3)
    assert read("flash_attn_roofline.train", run) == pytest.approx(want)
    assert 9.0 < want < 10.0
    # the numerator does not count kernels: half as many forward
    # events of twice the length read the same
    merged = {k: v for k, v in run["trace"]["ops_s"].items()
              if "flash_fwd" not in k}
    merged.update({FWD.format(i): 0.0240 for i in range(16)})
    assert read("flash_attn_roofline.train", dict(
        run, trace=dict(run["trace"], ops_s=merged))) == pytest.approx(want)
    seconds, bound = flops.roofline_seconds(
        ops, 16 * 12 * tensor, run["spec"]["peaks"]["TPU v5 lite"])
    assert bound == "compute" and seconds == pytest.approx(ops / 197e12)


# -- chipbench.scopes: the grouping ------------------------------------------

FORWARD = "jit(step)/jvp(Llama)/layer_3/sparkdl.attn/attn/q_proj/dot_general"
LORA = "jit(step)/jvp(Llama)/layer_3/sparkdl.attn/attn/q_proj/sparkdl.lora/dot"
BACKWARD = ("jit(step)/transpose(jvp(Llama))/jvp(Llama)/checkpoint/layer_3/"
            "sparkdl.mlp/mlp/down_proj/dot_general")
RECOMPUTE = ("jit(step)/transpose(jvp(Llama))/jvp(Llama)/checkpoint/"
             "rematted_computation/layer_3/sparkdl.attn/attn/"
             "sparkdl_flash_fwd/pallas_call")
HEAD = "jit(step)/transpose(jvp(sparkdl.lm_head_loss))/while/body/dot_general"


def test_scope_and_pass_of_a_name_stack():
    assert scopes.scope_of(FORWARD) == ("sparkdl.attn", "forward")
    assert scopes.scope_of(LORA) == ("sparkdl.lora", "forward")
    assert scopes.scope_of(BACKWARD) == ("sparkdl.mlp", "backward")
    assert scopes.scope_of(RECOMPUTE) == ("sparkdl.attn", "recompute")
    assert scopes.scope_of(HEAD) == ("sparkdl.lm_head_loss", "backward")
    assert scopes.scope_of("jit(step)/sparkdl.optimizer/add") == (
        "sparkdl.optimizer", "forward")
    assert scopes.scope_of("") == (scopes.UNSCOPED, "forward")


def test_by_scope_groups_self_times_and_counts_a_loop_once():
    stacks = scopes.op_names(
        '  %fusion.1 = bf16[8] fusion(%p), kind=kLoop, '
        f'metadata={{op_name="{FORWARD}" source_file="a.py"}}\n'
        f'  ROOT %while.5 = (s32[]) while(%t), metadata={{op_name="{HEAD}"}}\n'
        f'  %fusion.9 = f32[4] fusion(%h), metadata={{op_name="{HEAD}"}}\n'
        '  %copy.2 = bf16[8] copy(%x)\n')
    assert stacks == {"fusion.1": FORWARD, "while.5": HEAD, "fusion.9": HEAD}
    # one line of a trace: a while around two runs of its body's fusion
    line = [(0, 2_000_000, FORWARD), (2_000_000, 9_000_000, HEAD),
            (2_500_000, 3_000_000, HEAD), (6_000_000, 3_000_000, HEAD),
            (12_000_000, 1_000_000, "")]
    own = scopes.self_times(line)
    assert [ns for ns, _ in own] == [2e6, 3e6, 3e6, 3e6, 1e6]
    table = scopes.by_scope(own + [(6_000_000, RECOMPUTE),
                                   (4_000_000, BACKWARD)], steps=2)
    assert list(table) == ["sparkdl.lm_head_loss", "sparkdl.attn",
                           "sparkdl.mlp", scopes.UNSCOPED]
    assert table["sparkdl.lm_head_loss"] == pytest.approx({
        "forward": 0.0, "backward": 0.0045, "recompute": 0.0,
        "total": 0.0045})
    assert table["sparkdl.attn"] == pytest.approx({
        "forward": 0.001, "backward": 0.0, "recompute": 0.003,
        "total": 0.004})
    assert sum(r["total"] for r in table.values()) == pytest.approx(0.011)
