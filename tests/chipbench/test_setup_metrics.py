"""The four per-layer metrics that read ``setup_s`` from inside the
program (PR 37): a worker's reach of its chip, the probe child's, JAX's
own report of tracing and lowering, and what no span names, on made-up
runs in the shape of ``test_program_metrics.py``'s. No gang, no chip."""

import pytest

from chipbench import run as harness
from chipbench import setup_spans

CELL = "mistral7b-lora-train"
READERS = ("backend_init_s.train", "probe_backend_s.train",
           "trace_lower_s.train", "setup_unnamed_s.train")


def span(name, start, end, rank=None, **args):
    return {"name": name, "start": start, "end": end, "rank": rank,
            "cause": None, "launch_id": "1-0", "args": args}


def made_up_run():
    """A run started at t=1000 whose window opens at t=1052."""
    spec = harness.load_cell(CELL)
    spec["started"] = 1000.0
    return {
        "spec": spec, "device": {"kind": "TPU v5 lite"}, "chips": 1,
        "end_to_end": {"setup_s": 52.0, "train_tokens_per_s_per_chip": 7049.0},
        "launch_s": 27.5, "compile_s": 8.2, "trace": None,
        "launch_spans": [
            # named: 0.5 -> 28.0 without a gap (probe, spawn, boot,
            # connect, init, the chip, inside the driver's rendezvous)
            span("gang.slot_probe", 1000.5, 1015.5, cached=False,
                 child_boot_s=0.25, child_import_s=2.75,
                 child_backend_s=8.5, child_exit_s=3.5),
            span("gang.slot_probe", 1015.5, 1015.5, cached=True),
            span("gang.slot_claim", 1015.5, 1015.55),
            span("gang.spawn", 1015.55, 1015.6),
            span("worker.boot", 1015.6, 1018.5, rank=0),
            span("worker.connect", 1018.5, 1018.6, rank=0),
            span("hvd.init", 1018.6, 1018.6, rank=0),
            span("worker.backend", 1018.6, 1027.9, rank=0, platform="tpu",
                 devices=1, kind="TPU v5 lite"),
            span("gang.rendezvous", 1015.6, 1028.0),
            span("gang.ready", 1028.0, 1028.0),
            span("worker.job", 1028.5, 1100.0, rank=0),
            # weights: a compile of 2 s (30 -> 32)
            span("xla.compile", 1030.0, 1032.0, rank=0, program="jit(init)"),
            # the step: traced 36 -> 39 (the record keeps the outermost
            # trace of a thread, with the count of those inside it), with
            # another thread's trace inside it and one that overlaps its
            # end (39.5), lowered 39.5 -> 41, loaded 41 -> 45
            span("jax.trace", 1036.0, 1039.0, rank=0, program="step",
                 nested=300),
            span("jax.trace", 1036.5, 1037.5, rank=0, program="load"),
            span("jax.trace", 1038.5, 1039.5, rank=0, program="loss"),
            span("jax.trace", 1039.5, 1039.5, rank=0, program="instant"),
            span("jax.lower", 1039.5, 1041.0, rank=0, program="jit(step)"),
            span("xla.compile", 1041.0, 1045.0, rank=0, program="jit(step)",
                 cache="hit"),
            # another rank's are not rank 0's
            span("jax.trace", 1020.0, 1050.0, rank=1, program="step"),
            span("worker.backend", 1018.6, 1030.6, rank=1, platform="tpu"),
            # the reference check traces and compiles after the window
            span("jax.trace", 1085.0, 1088.0, rank=0, program="reference"),
            span("jax.lower", 1051.0, 1053.0, rank=0, program="jit(late)"),
            span("xla.compile", 1090.0, 1095.0, rank=0, program="jit(f)"),
        ]}


def read(name, run):
    return harness.load_reader(name)(run)


def test_the_four_readers_on_a_made_up_run():
    run = made_up_run()
    # the slowest rank's reach
    assert read("backend_init_s.train", run) == pytest.approx(12.0)
    alone = dict(run, launch_spans=[
        s for s in run["launch_spans"] if s["rank"] != 1])
    assert read("backend_init_s.train", alone) == pytest.approx(9.3)
    # the uncached probe's reach of the chip and its exit
    assert read("probe_backend_s.train", run) == pytest.approx(12.0)
    # rank 0's, ended before t = 1052: 36 -> 41 once, overlapping or not;
    # the late lowering (51 -> 53) does not count
    assert read("trace_lower_s.train", run) == pytest.approx(5.0)
    # 52 - (27.5 named to READY + 2 + (36 -> 45) 9 + 1) = 12.5:
    # worker.job names nothing, the compile after the window is not
    # set-up, the lowering across it (51 -> 53) counts as far as it
    assert read("setup_unnamed_s.train", run) == pytest.approx(12.5)
    named = 52.0 - read("setup_unnamed_s.train", run)
    assert named == pytest.approx(27.5 + 2.0 + 9.0 + 1.0)


def test_the_named_parts_and_the_unnamed_add_up_to_setup_s():
    run = made_up_run()
    started, window = setup_spans.stretch(run)
    assert (started, window) == (1000.0, 1052.0)
    spans = [s for s in run["launch_spans"] if s["rank"] in (None, 0)
             and s["name"] != "worker.job"]
    covered = setup_spans.covered_s(spans, started, window)
    assert covered + read("setup_unnamed_s.train", run) == pytest.approx(
        run["end_to_end"]["setup_s"])
    # clipping: a span across the window counts as far as the window,
    # one before the start not at all, overlapping ones once
    assert setup_spans.covered_s(
        [span("a", 990.0, 999.0), span("a", 1050.0, 1060.0),
         span("a", 1049.0, 1051.0), span("a", 1010.0, 1010.0)],
        started, window) == pytest.approx(3.0)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_returns_none_where_there_is_nothing_to_read(name):
    """A parent commit's record lacks the new spans and args (PR 24's
    are there); a run may have no launch at all: the metric is left out
    of the line, nothing raises."""
    run = made_up_run()
    assert read(name, {}) is None
    bare = {k: v for k, v in run.items()
            if k not in ("launch_spans", "launch_s")}
    assert read(name, bare) is None
    assert read(name, dict(run, launch_spans=[])) is None
    assert read(name, dict(run, launch_spans=None)) is None
    older = [dict(s, args={k: v for k, v in s["args"].items()
                           if not k.startswith("child_")})
             for s in run["launch_spans"]
             if s["name"] not in ("worker.backend", "jax.trace", "jax.lower")]
    assert read(name, dict(run, launch_spans=older)) is None
    # and with no setup_s there is no window to cut at
    if name in ("trace_lower_s.train", "setup_unnamed_s.train"):
        assert read(name, dict(run, end_to_end={})) is None


def test_the_new_metrics_are_the_mistral_cells_alone():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(READERS)
    for name in READERS:
        entry = entries[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "setup_s"
        assert (entry["unit"], entry["better"], entry["source"]) == (
            "s", "lower", "program_span")
