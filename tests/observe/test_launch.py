"""The always-on launch record: its bound, what a launch adopts, what
the driver takes from a worker, the operator's line, and a real
two-rank CPU gang with the telemetry latch unset."""

import logging

import pytest

from sparkdl_tpu import observe
from sparkdl_tpu.observe.launch import LaunchRecord, summary_line

PER_RANK = ("worker.boot", "worker.connect", "hvd.init", "worker.backend",
            "worker.job", "xla.compile")
DRIVER = ("gang.slot_probe", "gang.slot_claim", "gang.spawn",
          "gang.rendezvous", "gang.ready")


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
    for launch in range(4):
        ids.append(record.open())
        for i in range(4):
            record.add("xla.compile", launch * 10 + i, launch * 10 + i + 0.5)
        record.close(ids[-1])
        assert len(record) <= 10
    assert record.report(ids[0]) == [] and record.report(ids[1]) == []
    assert len(record.report(ids[2])) == len(record.report(ids[3])) == 4
    # one launch that outgrows the bound keeps its newest spans
    last = record.open()
    for i in range(25):
        record.add("xla.compile", 100 + i, 100.5 + i)
    assert len(record) == 10
    assert [s["start"] for s in record.report(last)] == list(range(115, 125))
    # and a process in which no launch ever opens (a worker between
    # two shipments) is bounded too
    worker = LaunchRecord(max_events=10)
    for i in range(25):
        worker.add("xla.compile", i, i + 0.5)
    assert len(worker) == 10 and len(worker.drain()) == 10
    assert len(worker) == 0


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


def test_summary_line_reads_without_a_tool():
    def span(name, start, end, rank=None):
        return {"name": name, "start": start, "end": end, "rank": rank,
                "cause": None, "launch_id": "x", "args": {}}

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
    job, *compiles = sorted(observe.launch_report(),
                            key=lambda s: s["name"] != "worker.job")
    assert job["name"] == "worker.job" and compiles
    ours = [s for s in compiles if s["args"]["program"] == "jit(<lambda>)"]
    assert len(ours) == 1
    assert all(s["name"] == "xla.compile" and s["cause"] == "worker.job"
               and job["start"] <= s["start"] <= s["end"] <= job["end"] + 1e-3
               for s in compiles)
    observe._reset_for_tests()                       # listeners gone
    jax.jit(lambda x: x * 5 + 2)(jnp.arange(7)).block_until_ready()
    assert observe.launch_report() == []


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
    assert len(observe.launch_record()) <= 60
