"""Timeline events, Chrome trace validity, the observe facade's
off-by-default contract (nothing recorded, nothing that outlives the
call), and profiler.annotate as an observe.span."""

import glob
import json
import os
import subprocess
import sys

import pytest

from sparkdl_tpu import observe
from sparkdl_tpu.observe.timeline import Timeline, chrome_trace


@pytest.fixture(autouse=True)
def fresh_observe(monkeypatch):
    monkeypatch.delenv(observe.TELEMETRY_DIR_ENV, raising=False)
    observe._reset_for_tests()
    yield
    observe._reset_for_tests()


# -- Timeline ----------------------------------------------------------------


def test_span_records_complete_event_with_duration():
    tl = Timeline()
    with tl.span("train_step", cat="train", step=3):
        pass
    (ev,) = tl.drain()
    assert ev["ph"] == "X" and ev["name"] == "train_step"
    assert ev["cat"] == "train" and ev["args"] == {"step": 3}
    assert isinstance(ev["ts"], int) and isinstance(ev["dur"], int)
    assert ev["dur"] >= 0 and ev["tid"] > 0


def test_span_records_even_when_body_raises():
    tl = Timeline()
    with pytest.raises(RuntimeError):
        with tl.span("boom"):
            raise RuntimeError("x")
    assert len(tl.drain()) == 1


def test_instant_shape_and_drain_empties():
    tl = Timeline()
    tl.instant("chaos.kill", cat="chaos", rank=1, step=2)
    (ev,) = tl.drain()
    assert ev["ph"] == "i" and ev["s"] == "p"
    assert ev["args"] == {"rank": 1, "step": 2}
    assert tl.drain() == []


def test_chrome_trace_is_valid_and_lane_labeled():
    tl = Timeline()
    tl.instant("late", cat="x")
    with tl.span("early", cat="x"):
        pass
    worker_events = tl.drain()
    doc = chrome_trace([
        (0, "driver", []),
        (2, "rank 1 @ hostA", worker_events),
    ])
    # Round-trips as JSON (what Perfetto loads).
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"driver", "rank 1 @ hostA"}
    # Metadata first, then chronological order.
    rest = events[len(metas):]
    assert all(e["pid"] == 2 for e in rest)
    assert [e["ts"] for e in rest] == sorted(e["ts"] for e in rest)


# -- facade: off by default, zero overhead -----------------------------------


def test_disabled_facade_records_nothing_and_keeps_nothing():
    import gc
    import weakref

    assert not observe.enabled()
    observe.inc("ops_total")
    observe.set_gauge("g", 1)
    observe.observe_value("h", 0.5)
    observe.instant("i")
    # The disabled span is only its annotation on the profiler's
    # clock (JAX is imported in this process): nothing is recorded,
    # and nothing the call made outlives it.
    s1 = observe.span("a", step=1)
    gone = weakref.ref(s1)
    with s1:
        assert not getattr(observe._open_spans, "stack", [])
    del s1
    gc.collect()
    assert gone() is None
    snap = observe.metrics().snapshot()
    assert snap["counters"] == snap["gauges"] == snap["histograms"] == []
    assert len(observe.timeline()) == 0
    assert len(observe.launch_record()) == 0
    # flush() without a sink (and disabled) is a no-op returning False
    assert observe.flush() is False


def test_enabled_facade_records(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    assert observe.enabled()
    observe.inc("ops_total", op="sum")
    observe.set_gauge("depth", 3)
    observe.observe_value("lat_seconds", 0.1)
    with observe.span("step", step=0):
        observe.instant("mark")
    snap = observe.metrics().snapshot()
    assert snap["counters"][0]["value"] == 1
    assert {e["name"] for e in observe.timeline().drain()} == \
        {"step", "mark"}


def test_flush_ships_payload_to_sink_and_drains(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    shipped = []
    observe.set_sink(shipped.append)
    observe.inc("c_total")
    observe.instant("ev")
    assert observe.flush() is True
    (payload,) = shipped
    assert payload["pid"] > 0 and payload["host"]
    assert payload["metrics"]["counters"][0]["name"] == "c_total"
    assert [e["name"] for e in payload["events"]] == ["ev"]
    # Events drained; metrics stay cumulative.
    assert observe.flush() is True
    assert shipped[1]["events"] == []
    assert shipped[1]["metrics"]["counters"][0]["value"] == 1


def test_sink_exceptions_never_propagate(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    observe.set_sink(lambda p: (_ for _ in ()).throw(OSError("gone")))
    observe.inc("c_total")
    assert observe.flush() is False


def test_flusher_start_stop(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    shipped = []
    observe.set_sink(shipped.append)
    t = observe.start_flusher(interval=0.01)
    assert observe.start_flusher(interval=0.01) is t  # idempotent
    import time as _time

    deadline = _time.time() + 5
    while not shipped and _time.time() < deadline:
        _time.sleep(0.01)
    observe.stop_flusher()
    assert shipped, "flusher never fired"
    assert not t.is_alive()


def test_new_run_dir_unique(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    a, b = observe.new_run_dir(), observe.new_run_dir()
    assert a != b
    import os

    assert os.path.isdir(a) and os.path.isdir(b)
    assert os.path.dirname(a) == str(tmp_path)


# -- profiler.annotate pairing ----------------------------------------------


def test_annotate_is_an_observe_span_of_cat_xprof(monkeypatch, tmp_path):
    """annotate() has no implementation of its own: with telemetry on
    the region lands in the gang timeline under its name (what lands
    in the xprof trace is test_span_lands_on_the_profilers_clock's)."""
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    from sparkdl_tpu.utils.profiler import annotate

    with annotate("attention-fwd"):
        pass
    (ev,) = observe.timeline().drain()
    assert ev["name"] == "attention-fwd"
    assert ev["cat"] == "xprof" and ev["ph"] == "X"


def test_annotate_is_inert_without_telemetry():
    from sparkdl_tpu.utils.profiler import annotate

    with annotate("region"):
        pass
    assert len(observe.timeline()) == 0


# -- one span call, on both clocks -------------------------------------------


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return {e.name for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events}


@pytest.mark.parametrize("telemetry", [False, True])
def test_span_lands_on_the_profilers_clock(telemetry, monkeypatch, tmp_path):
    """Under a profiler session a span is an event ``sparkdl.<name>``
    of the trace, whether or not the telemetry latch is set."""
    import jax
    import jax.numpy as jnp

    if telemetry:
        monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path / "t"))
        observe._reset_for_tests()
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        with observe.span("probe.outer", cat="train"):
            with observe.span("probe.inner", cat="collective"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(trace_dir)
    assert {"sparkdl.probe.outer", "sparkdl.probe.inner"} <= names
    assert len(observe.timeline()) == (2 if telemetry else 0)


def test_span_imports_no_jax_and_starts_no_thread():
    """In a process that has not imported JAX (the driver), a span
    imports nothing and starts nothing, recorded or not."""
    code = (
        "import sys, threading\n"
        "from sparkdl_tpu import observe\n"
        "with observe.span('a', cat='train'):\n"
        "    pass\n"
        "with observe.span('gang.x', cat='launch'):\n"
        "    observe.instant('gang.y', cat='launch')\n"
        "assert observe.watch_compiles() is False\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
        "assert threading.active_count() == 1\n"
        "assert [s['name'] for s in observe.launch_report()] == "
        "['gang.x', 'gang.y']\n"
        "print('ok')\n")
    env = {k: v for k, v in os.environ.items()
           if k != observe.TELEMETRY_DIR_ENV}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_recorded_span_names_its_cause_and_shares_its_identifier(
        monkeypatch, tmp_path):
    """What choosing-metrics asks of a span: name, start, end, the
    span that caused it (enclosing, or named) and an identifier the
    spans of one step share."""
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    with observe.span("train_step", cat="train", ident="train_step-7"):
        with observe.span("reduce", cat="collective"):
            pass
        with observe.span("late", cat="data", cause="input.next"):
            pass
    reduce, late, step = observe.timeline().drain()
    assert step["name"] == "train_step" and "cause" not in step["args"]
    assert reduce["args"] == {"cause": "train_step", "ident": "train_step-7"}
    assert late["args"] == {"cause": "input.next", "ident": "train_step-7"}
    assert step["ts"] <= reduce["ts"]
    assert reduce["ts"] + reduce["dur"] <= step["ts"] + step["dur"] + 1


def test_restart_context_emits_one_resume_marker(monkeypatch, tmp_path):
    """Mains may poll restart_context() every step; the merged
    timeline must show ONE gang.resume, not a wall of them."""
    import sparkdl_tpu.horovod as sh
    from sparkdl_tpu.horovod.supervisor import (
        RESTART_ATTEMPT_ENV,
        RESUME_STEP_ENV,
    )

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    monkeypatch.setenv(RESTART_ATTEMPT_ENV, "1")
    monkeypatch.setenv(RESUME_STEP_ENV, "7")
    monkeypatch.setattr(sh, "_resume_instant_emitted", False)
    for _ in range(5):
        ctx = sh.restart_context()
    assert (ctx.attempt, ctx.resume_step) == (1, 7)
    events = [e for e in observe.timeline().drain()
              if e["name"] == "gang.resume"]
    assert len(events) == 1
    args = events[0]["args"]
    assert args["attempt"] == 1
    assert args["resume_step"] == 7
