"""The always-on launch record: its bound, what a launch adopts, what
the driver takes from a worker, the operator's line, and a real
two-rank CPU gang with the telemetry latch unset."""

import logging

import pytest

from sparkdl_tpu import observe
from sparkdl_tpu.observe import launch as launch_module
from sparkdl_tpu.observe.launch import (
    LaunchRecord,
    job_line,
    phases,
    summary_line,
)

PER_RANK = ("worker.boot", "worker.connect", "hvd.init", "worker.backend",
            "worker.job", "xla.compile")
DRIVER = ("gang.slot_probe", "gang.slot_claim", "gang.spawn",
          "gang.rendezvous", "gang.ready")
JAX = ("jax.trace", "jax.lower", "xla.compile")


@pytest.fixture(autouse=True)
def fresh_observe(monkeypatch):
    monkeypatch.delenv(observe.TELEMETRY_DIR_ENV, raising=False)
    observe._reset_for_tests()
    yield
    observe._reset_for_tests()


def test_a_launch_adopts_what_was_recorded_before_it_opened():
    record = LaunchRecord()
    record.add("gang.slot_probe", 10.0, 18.0, cached=False)
    first = record.open()
    record.add("gang.spawn", 18.5, 18.6, num_workers=2)
    record.close(first)
    spans = record.report()
    assert [s["name"] for s in spans] == ["gang.slot_probe", "gang.spawn"]
    assert {s["launch_id"] for s in spans} == {first}
    assert spans[0]["args"] == {"cached": False} and spans[0]["rank"] is None
    # after the launch closed, spans wait for the next one
    record.add("gang.slot_probe", 30.0, 30.0, cached=True)
    second = record.open()
    assert second != first
    assert [s["name"] for s in record.report()] == ["gang.slot_probe"]
    assert [s["name"] for s in record.report(first)] == [
        "gang.slot_probe", "gang.spawn"]


def test_the_record_is_bounded_and_drops_the_oldest_launch_first():
    record = LaunchRecord(max_events=10)
    ids = []
    for launch in range(12):
        ids.append(record.open())
        for i in range(4):
            record.add("xla.compile", launch * 10 + i, launch * 10 + i + 0.5)
        record.close(ids[-1])
        assert len(record) <= 4 * 10        # all together: four parts
    assert record.report(ids[0]) == [] and record.report(ids[1]) == []
    assert len(record.report(ids[2])) == len(record.report(ids[11])) == 4
    # one launch that outgrows the bound keeps its newest spans
    last = record.open()
    for i in range(25):
        record.add("xla.compile", 200 + i, 200.5 + i)
    assert [s["start"] for s in record.report(last)] == list(range(215, 225))
    assert len(record) <= 4 * 10
    # and a process in which no launch ever opens (a worker between
    # two shipments) is bounded too
    worker = LaunchRecord(max_events=10)
    for i in range(25):
        worker.add("xla.compile", i, i + 0.5)
    assert len(worker) == 10 and len(worker.drain()) == 10
    assert len(worker) == 0


def _frames(programs):
    """A rank's two LAUNCH frames: what it ships before READY, and a
    job of `programs` programs before BYE."""
    ready = [{"name": n, "start": 1.0 + i, "end": 2.0 + i, "args": {}}
             for i, n in enumerate(
                 ("worker.boot", "worker.connect", "hvd.init",
                  "worker.backend"))]
    bye = [{"name": n, "start": 10.0 + i, "end": 10.5 + i,
            "cause": "worker.job", "args": {"program": f"jit(p{i})"}}
           for i in range(programs)
           for n in ("jax.trace", "jax.lower", "xla.compile")]
    bye.append({"name": "worker.job", "start": 9.0, "end": 11.0 + programs,
                "args": {}})
    return ready, bye


@pytest.mark.parametrize("np, programs, kept", [
    (4, 83, 3 * 83),        # a job as deep as the Mistral cell's was: whole
    (4, 300, 400 - 5),      # a part that outgrows the bound: its newest
    (64, 83, 3 * 83),       # a gang of any size
], ids=["np4", "np4-overgrown", "np64"])
def test_the_bound_is_a_ranks_and_never_takes_what_a_launch_has_once(
        np, programs, kept):
    """The bound is of one process's part of a launch, each rank's and
    the driver's own apart: no rank's frames push out the driver's
    ``gang.*`` spans, another rank's READY frame or rank 0's job, and
    what goes from a part that outgrows it is the oldest of JAX's
    reports."""
    record = LaunchRecord()
    record.add("gang.slot_probe", 0.0, 0.9, cached=False)
    launch = record.open()
    for name in ("gang.slot_claim", "gang.spawn"):
        record.add(name, 0.9, 1.0)
    ready, bye = _frames(programs)
    for rank in range(np):
        record.ingest(launch, rank, ready)
    record.add("gang.rendezvous", 1.0, 6.0)
    record.add("gang.ready", 6.0, 6.0)
    for rank in range(np):
        record.ingest(launch, rank, bye)
    spans = record.report(launch)
    assert [s["name"] for s in spans if s["rank"] is None] == list(DRIVER)
    for rank in range(np):
        part = [s for s in spans if s["rank"] == rank]
        assert len(part) <= launch_module.MAX_EVENTS
        once = [s["name"] for s in part if s["name"] not in JAX]
        assert sorted(once) == sorted(PER_RANK[:5])
        reports = [s for s in part if s["name"] in JAX]
        assert len(reports) == kept
        # the newest: the last program's three are there
        assert [s["args"]["program"] for s in reports[-3:]] == [
            f"jit(p{programs - 1})"] * 3
    assert "chip 1.0 s" in summary_line(spans) and "boot 1.0 s" in (
        summary_line(spans))
    compiles = sum(s["name"] == "xla.compile" and s["rank"] == 0
                   for s in spans)
    assert f" s ({compiles} programs: " in job_line(spans)
    # a part of nothing but what a launch has once is bounded too
    for i in range(500):
        record.add("gang.slot_probe", 20.0 + i, 20.0 + i, cached=True)
    assert len([s for s in record.report(launch) if s["rank"] is None]) == (
        launch_module.MAX_EVENTS)


def test_ingest_stamps_rank_launch_and_cause_and_drops_malformed_spans():
    record = LaunchRecord()
    launch = record.open()
    shipped = [
        {"name": "worker.boot", "start": 1.0, "end": 3.0, "cause": None,
         "launch_id": None, "rank": None, "args": {}},
        {"name": "xla.compile", "start": 4.0, "end": 4.5,
         "cause": "worker.job", "args": {"program": "jit(step)"}},
        {"name": "no times"}, "not a dict", None,
    ]
    record.ingest(launch, 1, shipped)
    record.ingest(launch, 0, {"not": "a list"})
    record.ingest("unknown-launch", 0, shipped)
    boot, compile_ = record.report(launch)
    assert boot == {"name": "worker.boot", "start": 1.0, "end": 3.0,
                    "cause": "gang.spawn", "launch_id": launch, "rank": 1,
                    "args": {}}
    assert compile_["cause"] == "worker.job" and compile_["rank"] == 1
    assert compile_["args"] == {"program": "jit(step)"}


def span(name, start, end, rank=None, **args):
    return {"name": name, "start": start, "end": end, "rank": rank,
            "cause": None, "launch_id": "x", "args": args}


def test_summary_line_reads_without_a_tool():
    spans = [
        span("gang.slot_probe", 0.0, 8.0), span("gang.slot_probe", 8.5, 8.5),
        span("gang.slot_claim", 8.5, 8.6), span("gang.spawn", 8.6, 8.7),
        span("worker.boot", 8.65, 14.0, 0), span("worker.boot", 8.7, 15.2, 1),
        span("worker.connect", 14.0, 14.1, 0), span("hvd.init", 14.1, 17.0, 0),
        span("hvd.init", 15.3, 17.0, 1), span("gang.rendezvous", 8.7, 17.95),
        span("gang.ready", 18.0, 18.0), span("worker.job", 18.0, 90.0, 0),
        span("xla.compile", 30.0, 42.0, 0),
    ]
    assert summary_line(spans) == (
        "gang ready in 18.0 s: slot probe 8.0 s, slot claim 0.1 s, "
        "spawn 0.1 s, boot 6.5 s, connect 0.1 s, hvd.init 2.9 s, "
        "rendezvous 9.2 s")
    assert "no launch spans" in summary_line([])
    # a single worker's reach of its chip comes before READY: on the line
    chip = spans + [span("worker.backend", 17.0, 17.9, 0, platform="tpu")]
    assert "hvd.init 2.9 s, chip 0.9 s, rendezvous" in summary_line(chip)


@pytest.mark.parametrize("intervals, until, seconds", [
    ([(0.0, 4.0), (1.0, 2.0)], None, 4.0),                # nested
    ([(0.0, 4.0), (3.0, 6.0)], None, 6.0),                # overlapping
    ([(5.0, 6.0), (0.0, 1.0), (0.5, 1.5)], None, 2.5),    # in any order
    ([(0.0, 4.0), (3.0, 6.0), (9.0, 10.0)], 6.0, 6.0),    # cut at `until`
    ([(2.0, 2.0)], None, 0.0),                            # an instant
], ids=["nested", "overlapping", "unsorted", "until", "zero-length"])
def test_phases_takes_unions(intervals, until, seconds):
    spans = [span("jax.trace", a, b, 0) for a, b in intervals]
    spans.append(span("jax.lower", 20.0, 21.0, 0))
    took = phases(spans, until=until)
    assert took["jax.trace"] == pytest.approx(seconds)
    assert ("jax.lower" in took) == (until is None)


JOB = [
    span("gang.ready", 18.0, 18.0),
    span("worker.backend", 8.0, 17.8, 0, platform="tpu", devices=1,
         kind="TPU v5 lite"),
    span("worker.job", 18.0, 90.0, 0),
    span("jax.trace", 20.0, 23.0, 0, program="step", nested=179),
    span("jax.trace", 20.5, 21.5, 0, program="load", nested=0),  # a thread's
    span("jax.lower", 23.0, 24.25, 0, program="jit(step)", nested=0),
    span("xla.compile", 24.25, 28.25, 0, program="jit(step)", cache="hit"),
    span("xla.compile", 30.0, 31.5, 0, program="jit(init)", cache="miss"),
    span("xla.compile", 30.0, 39.0, 1, program="jit(step)", cache="miss"),
]


@pytest.mark.parametrize("line, spans, wants", [
    (job_line, JOB, "job setup (rank 0): chip 9.8 s, trace 3.0 s (+179 "
     "nested), lower 1.2 s, compile 5.5 s (2 programs: 1 from "
     "the cache, 1 compiled), longest jit(step) 8.2 s"),
    (lambda spans: job_line(spans, rank=1), JOB, "job setup (rank 1): "
     "compile 9.0 s (1 programs: 0 from the cache, 1 compiled), longest "
     "jit(step) 9.0 s"),
    (job_line, JOB[:1], "job setup (rank 0): no job spans recorded"),
    (summary_line, JOB, "gang ready in 10.0 s: chip 9.8 s (1 x TPU v5 lite)"),
], ids=["rank 0", "rank 1", "no job", "gang ready"])
def test_the_lines_print_what_phases_returns(line, spans, wants):
    assert line(spans) == wants
    took = phases([s for s in spans if s["rank"] == 0])
    for label, name in (("chip", "worker.backend"), ("trace", "jax.trace")):
        if f"{label} " in wants and "rank 1" not in wants:
            assert f"{label} {took[name]:.1f} s" in wants


def test_launch_spans_are_recorded_with_telemetry_off_and_only_once_with_it_on(
        monkeypatch, tmp_path):
    assert not observe.enabled()
    with observe.span("gang.slot_claim", cat="launch", num_workers=2):
        observe.instant("gang.ready", cat="launch")
    observe.complete("worker.boot", 5.0, 2.0, cat="launch")
    names = [s["name"] for s in observe.launch_report()]
    assert names == ["worker.boot", "gang.slot_claim", "gang.ready"]
    ready = observe.launch_report()[-1]
    assert ready["cause"] == "gang.slot_claim" and ready["start"] == ready["end"]
    assert len(observe.timeline()) == 0
    # with the latch set the same events go to the timeline as before,
    # and to the record once
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    with observe.span("gang.slot_claim", cat="launch", num_workers=2):
        pass
    assert [e["name"] for e in observe.timeline().drain()] == [
        "gang.slot_claim"]
    assert len(observe.launch_report()) == 1


def test_watch_compiles_records_each_backend_compile_once():
    import jax
    import jax.numpy as jnp

    assert observe.watch_compiles() is True
    assert observe.watch_compiles() is True          # registered once
    with observe.span("worker.job", cat="launch"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    job, *reported = sorted(observe.launch_report(),
                            key=lambda s: s["name"] != "worker.job")
    assert job["name"] == "worker.job"
    compiles = [s for s in reported if s["name"] == "xla.compile"]
    ours = [s for s in compiles if s["args"]["program"] == "jit(<lambda>)"]
    assert len(ours) == 1
    assert {s["name"] for s in reported} == {
        "jax.trace", "jax.lower", "xla.compile"}
    assert all(s["cause"] == "worker.job"
               and job["start"] <= s["start"] <= s["end"] <= job["end"] + 1e-3
               for s in reported)
    observe._reset_for_tests()                       # listeners gone
    jax.jit(lambda x: x * 5 + 2)(jnp.arange(7)).block_until_ready()
    assert observe.launch_report() == []


def _nested():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2

    def outer(x):
        return inner(x).sum()

    return jax.jit(outer), jnp.arange(7.0)


def _jax_reports():
    """What JAX itself reported since, by its own listener: [(event's
    last word, start, end)]."""
    from jax._src import monitoring

    seen = []

    def listener(event, start, end, **_):
        seen.append((event.rsplit("/", 1)[-1], start, end))

    monitoring.register_event_time_span_listener(listener)
    return seen, lambda: monitoring.unregister_event_time_span_listener(
        listener)


def test_watch_compiles_records_trace_lower_and_compile():
    """JAX's own report of a program's tracing and lowering beside its
    compile, by its own start and end. A `jit` inside a `jit` reports
    inside its caller's interval: the outermost is the span and counts
    it; every compile is its own span."""
    assert observe.watch_compiles() is True
    seen, stop = _jax_reports()
    try:
        step, x = _nested()
        step(x).block_until_ready()
    finally:
        stop()
    spans = observe.launch_report()
    assert all(s["start"] <= s["end"] for s in spans)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    compiles = by_name["xla.compile"]
    assert len(compiles) == sum(e == "backend_compile_duration"
                                for e, _, _ in seen)
    assert "jit(outer)" in [s["args"]["program"] for s in compiles]
    reported = {e: [(a, b) for e2, a, b in seen if e2 == e]
                for e in ("jaxpr_trace_duration",
                          "jaxpr_to_mlir_module_duration")}
    traces = {s["args"]["program"]: s for s in by_name["jax.trace"]}
    assert "inner" not in traces            # JAX reported it, inside outer
    outer = traces["outer"]
    inside = [(a, b) for a, b in reported["jaxpr_trace_duration"]
              if outer["start"] <= a and b <= outer["end"]]
    assert len(inside) >= 2 and (outer["start"], outer["end"]) in inside
    assert outer["args"]["nested"] == len(inside) - 1
    # the spans are the reports no other report encloses, and no more
    for name, event in (("jax.trace", "jaxpr_trace_duration"),
                        ("jax.lower", "jaxpr_to_mlir_module_duration")):
        everything = [r for rs in reported.values() for r in rs]
        outermost = [(a, b) for a, b in reported[event] if not any(
            (c, d) != (a, b) and c <= a and b <= d for c, d in everything)]
        assert [(s["start"], s["end"]) for s in by_name[name]] == sorted(
            outermost)
    assert "jit(outer)" in [s["args"]["program"] for s in by_name["jax.lower"]]
    assert sum(s["args"]["nested"] for s in by_name["jax.trace"]
               + by_name["jax.lower"]) + len(
        by_name["jax.trace"] + by_name["jax.lower"]) == len(
        reported["jaxpr_trace_duration"]
        + reported["jaxpr_to_mlir_module_duration"])


@pytest.mark.parametrize("threads", [1, 3])
def test_a_models_depth_leaves_no_more_spans(threads):
    """A step that enters an inner `jit` a layer leaves the spans of
    one program whatever its depth, in every thread that traces."""
    import threading

    import jax
    import jax.numpy as jnp

    assert observe.watch_compiles() is True
    x = jnp.arange(5.0)
    layer = jax.jit(lambda x, w: jnp.tanh(x * w))

    def run(depth):
        def step(x):
            for i in range(depth):
                x = jax.jit(lambda x, w=float(i): layer(x, w))(x)
            return x.sum()

        jax.jit(step)(x).block_until_ready()

    def spans_of(depth):
        before = len(observe.launch_record())
        workers = [threading.Thread(target=run, args=(depth,))
                   for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        return len(observe.launch_record()) - before

    assert spans_of(2) == spans_of(40) == 3 * threads
    steps = sorted(s["args"]["nested"] for s in observe.launch_report()
                   if s["name"] == "jax.trace"
                   and s["args"]["program"] == "step")
    assert len(steps) == 2 * threads
    # what another thread has traced is not traced again: at least
    assert steps[threads - 1] < 40 <= steps[threads]


def test_a_jax_without_time_spans_is_read_by_its_durations(monkeypatch):
    import jax

    monkeypatch.delattr(jax.monitoring, "register_event_time_span_listener")
    assert observe.watch_compiles() is True
    seen, stop = _jax_reports()
    try:
        step, x = _nested()
        step(x).block_until_ready()
    finally:
        stop()
    spans = observe.launch_report()
    assert {s["name"] for s in spans} == {
        "jax.trace", "jax.lower", "xla.compile"}
    assert len(spans) + sum(
        s["args"].get("nested", 0) for s in spans) == len(seen)
    # now - duration: the same lengths, ends within the listener's call
    for s in spans:
        assert any(
            s["end"] - s["start"] == pytest.approx(end - start, abs=1e-4)
            and 0.0 <= s["end"] - end < 0.05 for _, start, end in seen)
    observe._reset_for_tests()                       # and unregisters
    jax.jit(lambda x: x - 1)(x).block_until_ready()
    assert observe.launch_report() == []


def test_a_compiled_step_called_in_a_loop_adds_no_span():
    assert observe.watch_compiles() is True
    step, x = _nested()
    step(x).block_until_ready()
    before = len(observe.launch_record())
    assert before > 0
    for _ in range(20):
        step(x).block_until_ready()
    assert len(observe.launch_record()) == before


def _probe_child(lines):
    """A `subprocess.run` whose child prints `lines` (a callable of the
    clock at its start, to stamp them)."""
    import subprocess
    import time

    def run(argv, **_):
        started = time.time()
        return subprocess.CompletedProcess(
            argv, 0, stdout="\n".join(lines(started)) + "\n", stderr="")
    return run


@pytest.mark.parametrize("lines, child", [
    (lambda t: ["4 tpu 2,2,1",
                f"sparkdl-probe-times {t + 0.25} 3.5 7.25 {t + 11.0}"], True),
    # an older worker image's child prints the one line
    (lambda t: ["WARNING: something on stdout", "4 tpu 2,2,1"], False),
], ids=["times", "old line"])
def test_the_slot_probe_says_where_its_childs_seconds_went(
        monkeypatch, lines, child):
    from sparkdl_tpu.horovod import launcher

    monkeypatch.setattr(launcher.subprocess, "run", _probe_child(lines))
    found = launcher.probe_local_devices(None)
    assert found == launcher.LocalDevices(4, "tpu", (2, 2, 1))
    assert launcher.probe_local_devices(None) == found
    first, cached = observe.launch_report()
    assert first["args"].pop("cached") is False
    # the cached answer records none of them, as before
    assert cached["args"] == {"cached": True}
    # the operator's line says where the child's seconds went
    assert ("s, import 3.5 s, chip 7.2 s, exit -11.0 s)" in summary_line(
        observe.launch_report())) == child
    # what answered: the child, and how many devices it found
    assert first["args"].pop("source") == "child"
    assert first["args"].pop("chips") == 4
    if not child:
        assert first["args"] == {}
        return
    args = first["args"]
    assert sorted(args) == ["child_backend_s", "child_boot_s",
                            "child_exit_s", "child_import_s"]
    assert args["child_import_s"] == 3.5 and args["child_backend_s"] == 7.25
    # the fake's clock: its first line 0.25 s after the span's start,
    # its last 11 s after it, that is "11 s before" the span's end
    assert args["child_boot_s"] == pytest.approx(0.25, abs=0.05)
    assert args["child_exit_s"] == pytest.approx(-11.0, abs=0.05)
    assert first["start"] + args["child_boot_s"] == pytest.approx(
        first["end"] - args["child_exit_s"] - 10.75, abs=0.05)


def _job():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd

    hvd.init()
    jax.jit(lambda x: x * 2)(jnp.ones(4)).block_until_ready()
    hvd.allreduce(np.ones(4, np.float32))
    return hvd.size()


@pytest.mark.gang
def test_two_rank_gang_reports_every_launch_span_with_telemetry_unset(
        monkeypatch, caplog):
    """The spans of ISSUE 24 (d) for both ranks of a real CPU gang,
    with nothing set: each has a launch, a rank and a cause, starts
    before it ends, and lies inside the span around it; the launcher
    says so in one INFO line; a second launch stays inside the bound."""
    import threading

    from sparkdl_tpu.horovod import launcher
    from sparkdl_tpu.horovod.runner_base import HorovodRunner

    monkeypatch.setattr(observe.launch_record(), "_max", 60)
    assert not observe.enabled()
    threads = {t.name for t in threading.enumerate()}
    # what the benchmark's harness does before run(): its own probe
    # (conftest's fixture leaves every test a cold cache)
    launcher.probe_local_devices("cpu")
    with caplog.at_level(logging.INFO, logger="HorovodRunner"):
        assert HorovodRunner(np=2).run(_job) == 2
    # no thread of the record's: what may linger is the control
    # plane's accept loop, which every launch has always had
    assert {t.name for t in threading.enumerate()} - threads <= {
        "sparkdl-tpu-control-accept"}
    spans = observe.launch_report()
    launch = {s["launch_id"] for s in spans}
    assert len(launch) == 1 and None not in launch
    driver = [s for s in spans if s["rank"] is None]
    assert {s["name"] for s in driver} >= set(DRIVER)
    assert all(s["cause"] is None for s in driver)
    probes = [s for s in driver if s["name"] == "gang.slot_probe"]
    assert [p["args"]["cached"] for p in probes][:2] == [False, True]
    for rank in (0, 1):
        mine = {s["name"]: s for s in spans if s["rank"] == rank}
        assert set(mine) >= set(PER_RANK), (rank, sorted(mine))
        for name in ("worker.boot", "worker.connect", "hvd.init",
                     "worker.job"):
            assert mine[name]["cause"] == "gang.spawn"
        assert mine["worker.backend"]["cause"] == "hvd.init"
        assert mine["xla.compile"]["cause"] in ("worker.job", "hvd.init")
        order = [mine[n] for n in ("worker.boot", "worker.connect",
                                   "hvd.init", "worker.job")]
        assert all(a["end"] <= b["start"] + 1e-3
                   for a, b in zip(order, order[1:]))
    by_name = {}
    for s in spans:
        assert s["start"] <= s["end"]
        by_name.setdefault((s["rank"], s["name"]), s)
    spawn = by_name[None, "gang.spawn"]
    for s in spans:
        if s["rank"] is None or s["cause"] is None:
            continue
        if s["cause"] == "gang.spawn":      # caused by, not inside
            assert spawn["start"] <= s["start"] + 1.0
        else:                               # enclosed on its thread
            around = by_name[s["rank"], s["cause"]]
            assert around["start"] - 1e-3 <= s["start"]
            assert s["end"] <= around["end"] + 1e-3
    ready = by_name[None, "gang.ready"]
    assert all(by_name[r, "hvd.init"]["end"] <= ready["end"] + 1e-3
               for r in (0, 1))
    (line,) = [r.getMessage() for r in caplog.records
               if r.getMessage().startswith("gang ready in ")]
    for label in ("slot probe", "spawn", "boot", "hvd.init", "rendezvous"):
        assert f"{label} " in line, line

    # a second launch is another launch, and the record keeps its bound
    assert HorovodRunner(np=2).run(_job) == 2
    again = observe.launch_report()
    assert {s["launch_id"] for s in again}.isdisjoint(launch)
    assert {s["name"] for s in again if s["rank"] == 1} >= set(PER_RANK)
    # both launches are held: the bound is a process's part of one
    assert len(again) <= 60 and len(observe.launch_record()) <= 2 * 60


def _job_that_asks(fail=False):
    """Whether the backend was up when the job's `main` was entered."""
    from jax._src import xla_bridge

    up = xla_bridge.backends_are_initialized()
    if fail:
        raise ValueError(f"the job's own fault (backend up: {up})")
    return up, _job()


@pytest.mark.gang
@pytest.mark.parametrize("np", [1, 2])
def test_every_rank_reaches_its_chip_once_before_ready(np, caplog):
    """``worker.backend`` whatever the gang's size: one a rank a launch
    (a single worker's straight after ``hvd.init``, a gang's inside
    it), saying what answered, shipped before READY, so the job's
    `main` finds the backend up; and the launcher's two lines, one for
    the gang and one for the job."""
    from sparkdl_tpu.horovod.runner_base import HorovodRunner

    with caplog.at_level(logging.INFO, logger="HorovodRunner"):
        assert HorovodRunner(np=np).run(_job_that_asks) == (True, np)
    spans = observe.launch_report()
    (ready,) = [s for s in spans if s["name"] == "gang.ready"]
    reaches = [s for s in spans if s["name"] == "worker.backend"]
    assert sorted(s["rank"] for s in reaches) == list(range(np))
    for reach in reaches:
        assert reach["args"]["platform"] == "cpu"
        assert reach["args"]["devices"] >= 1 and reach["args"]["kind"]
        assert reach["end"] <= ready["start"]
        assert reach["cause"] == ("gang.spawn" if np == 1 else "hvd.init")
        (boot,) = [s for s in spans if s["name"] == "worker.boot"
                   and s["rank"] == reach["rank"]]
        assert boot["end"] <= reach["start"]
    lines = [r.getMessage() for r in caplog.records]
    (gang,) = [m for m in lines if m.startswith("gang ready in ")]
    (job,) = [m for m in lines if m.startswith("job setup (rank 0): ")]
    assert " chip " in gang and lines.index(gang) < lines.index(job)
    assert job == job_line(spans)
    assert "chip " in job and "compile " in job and "programs: " in job


@pytest.mark.gang
def test_a_failed_job_still_gets_its_line(caplog):
    from sparkdl_tpu.horovod.runner_base import HorovodRunner

    with caplog.at_level(logging.INFO, logger="HorovodRunner"):
        with pytest.raises(RuntimeError, match="backend up: True"):
            HorovodRunner(np=1).run(_job_that_asks, fail=True)
    lines = [r.getMessage() for r in caplog.records]
    (job,) = [m for m in lines if m.startswith("job setup (rank 0): ")]
    assert job.startswith("job setup (rank 0): chip ")
