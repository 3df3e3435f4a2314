"""observe.perf: step-time attribution on synthetic timelines, the
per-device-kind peak table, roofline/MFU gauges, the regression
ledger, and the zero-overhead latch (ISSUE 7 tentpole). All tier-1:
no gang, no jax required for the attribution math."""

import json
import os

import pytest

from sparkdl_tpu import observe
from sparkdl_tpu.observe import perf
from sparkdl_tpu.observe.aggregate import GangTelemetry


@pytest.fixture(autouse=True)
def fresh_observe(monkeypatch):
    monkeypatch.delenv(observe.TELEMETRY_DIR_ENV, raising=False)
    monkeypatch.delenv(perf.PEAK_FLOPS_ENV, raising=False)
    monkeypatch.delenv(perf.PEAK_BYTES_ENV, raising=False)
    observe._reset_for_tests()
    yield
    observe._reset_for_tests()


US = 1000  # µs per ms


def span(name, cat, ts_ms, dur_ms, tid, **args):
    return {"name": name, "cat": cat, "ph": "X", "ts": ts_ms * US,
            "dur": dur_ms * US, "tid": tid, "args": args}


# -- attribution math --------------------------------------------------------


def test_serialized_collectives_block_the_step_thread():
    """Collective spans on the step span's own thread are serialized:
    they count as collective wall time, compute is the remainder, and
    overlap efficiency is 0 — today's barrier-style ops."""
    evs = [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("reduce", "collective", 10, 20, tid=1),
        span("allgather", "collective", 50, 10, tid=1),
    ]
    (row,) = perf.step_breakdown(evs)
    assert row["components"]["collective"] == pytest.approx(0.030)
    assert row["components"]["compute"] == pytest.approx(0.070)
    assert row["overlap_efficiency"] == 0.0
    assert row["overlapped_collective_s"] == 0.0
    # the wall-time components sum to the step span by construction
    assert sum(row["components"].values()) == pytest.approx(
        row["dur_s"], rel=1e-6)


def test_fully_overlapped_collectives_dont_eat_compute():
    """A collective span on ANOTHER thread while the step thread is
    computing is async/overlapped: compute stays the full step, the
    overlapped time is reported separately, efficiency is 1.0 — the
    after picture of ROADMAP item 3's async-collective work."""
    evs = [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("reduce", "collective", 10, 30, tid=2),
    ]
    (row,) = perf.step_breakdown(evs)
    assert row["components"]["compute"] == pytest.approx(0.100)
    assert row["components"]["collective"] == 0.0
    assert row["overlapped_collective_s"] == pytest.approx(0.030)
    assert row["overlap_efficiency"] == pytest.approx(1.0)


def test_partially_overlapped_collective():
    """An off-thread collective only counts as overlapped while the
    step thread is actually computing — the slice spent inside a
    same-thread wait is not overlap."""
    evs = [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("checkpoint.save", "checkpoint", 0, 20, tid=1),
        span("reduce", "collective", 10, 30, tid=2),  # 10ms under ckpt
    ]
    (row,) = perf.step_breakdown(evs)
    assert row["overlapped_collective_s"] == pytest.approx(0.020)
    assert row["collective_total_s"] == pytest.approx(0.030)
    assert row["overlap_efficiency"] == pytest.approx(2 / 3)
    assert row["components"]["checkpoint"] == pytest.approx(0.020)


def test_nested_collective_spans_never_double_count():
    """allgather internally calls reduce (size exchange): nested spans
    on the same thread must union, not sum."""
    evs = [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("allgather", "collective", 40, 30, tid=1),
        span("reduce", "collective", 45, 10, tid=1),  # inside allgather
    ]
    (row,) = perf.step_breakdown(evs)
    assert row["components"]["collective"] == pytest.approx(0.030)


def test_all_categories_attributed_and_sum_holds():
    evs = [
        span("train_step", "train", 0, 100, tid=7, step=0),
        span("reduce", "collective", 5, 10, tid=7),
        span("callback", "host", 20, 5, tid=7),
        span("data.wait", "data", 30, 15, tid=7),
        span("checkpoint.save", "checkpoint", 60, 20, tid=7),
    ]
    (row,) = perf.step_breakdown(evs)
    c = row["components"]
    assert c["collective"] == pytest.approx(0.010)
    assert c["host_callback"] == pytest.approx(0.005)
    assert c["data_wait"] == pytest.approx(0.015)
    assert c["checkpoint"] == pytest.approx(0.020)
    assert c["compute"] == pytest.approx(0.050)
    assert sum(c.values()) == pytest.approx(row["dur_s"])


def test_compile_phase_step_span_is_excluded():
    """instrument_step's first call is XLA compile wall time
    (phase="compile"): attributing it would report a 30s compile as
    "compute" and mask the real split. Only execute-phase spans are
    broken down."""
    evs = [
        span("train_step", "train", 0, 30000, tid=1, step=0,
             phase="compile"),
        span("train_step", "train", 30000, 100, tid=1, step=1,
             phase="execute"),
        span("reduce", "collective", 30010, 20, tid=1),
    ]
    rows = perf.step_breakdown(evs)
    assert len(rows) == 1
    assert rows[0]["step"] == 1
    assert rows[0]["dur_s"] == pytest.approx(0.100)
    assert rows[0]["components"]["collective"] == pytest.approx(0.020)


def test_zero_span_step_is_harmless():
    """A zero-duration step span (a clock with no resolution, a span
    torn at a kill) must not divide by zero."""
    (row,) = perf.step_breakdown(
        [span("train_step", "train", 5, 0, tid=1)])
    assert row["dur_s"] == 0.0
    assert row["overlap_efficiency"] is None
    assert row["components"]["compute"] == 0.0


def test_spans_outside_the_step_window_are_clipped():
    evs = [
        span("train_step", "train", 50, 50, tid=1, step=1),
        # straddles the step start: only the inside half counts
        span("reduce", "collective", 30, 40, tid=1),
    ]
    (row,) = perf.step_breakdown(evs)
    assert row["components"]["collective"] == pytest.approx(0.020)


def test_attribution_report_aggregates_and_keeps_schema():
    evs = [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("reduce", "collective", 10, 20, tid=1),
        span("train_step", "train", 200, 100, tid=1, step=1),
        span("reduce", "collective", 210, 20, tid=2),
    ]
    rep = perf.attribution_report(evs)
    assert rep["schema"] == perf.BREAKDOWN_SCHEMA
    assert rep["steps"] == 2
    assert rep["total_s"] == pytest.approx(0.200)
    assert rep["components"]["collective"] == pytest.approx(0.020)
    assert rep["overlapped_collective_s"] == pytest.approx(0.020)
    assert rep["overlap_efficiency"] == pytest.approx(0.5)
    assert len(rep["per_step"]) == 2
    # components (step-thread wall time) sum to total step time
    assert sum(rep["components"].values()) == pytest.approx(
        rep["total_s"], rel=0.05)


def test_inter_step_data_wait_reported_outside_windows():
    """The canonical `for batch in prefetch: stepped(batch)` pattern
    refills BETWEEN step spans — a starved pipeline must surface as
    inter_step_data_wait_s, not vanish because the spans clip away
    from every step window."""
    evs = [
        span("train_step", "train", 0, 100, tid=1, step=0),
        # the refill between the steps: 80ms of host starvation
        span("data.wait", "data", 100, 80, tid=1),
        span("train_step", "train", 180, 100, tid=1, step=1),
        # a wait INSIDE a step window still lands in the component...
        span("data.wait", "data", 190, 10, tid=1),
    ]
    rep = perf.attribution_report(evs)
    assert rep["inter_step_data_wait_s"] == pytest.approx(0.080)
    assert rep["components"]["data_wait"] == pytest.approx(0.010)
    # ...and the in-window slice never double-counts into inter-step
    assert sum(rep["components"].values()) == pytest.approx(
        rep["total_s"], rel=1e-6)


def test_attribution_report_empty_timeline():
    assert perf.attribution_report([]) == {"steps": 0}
    assert perf.attribution_report(
        [span("reduce", "collective", 0, 5, tid=1)]) == {"steps": 0}


def test_make_breakdown_schema_shared_with_step_breakdown_bench():
    doc = perf.make_breakdown(
        0.02, {"forward": 0.005, "backward": 0.012, "optimizer": 0.003},
        source="measured")
    assert doc["schema"] == perf.BREAKDOWN_SCHEMA
    assert doc["fractions"]["backward"] == pytest.approx(0.6)
    zero = perf.make_breakdown(0.0, {"forward": 0.0}, source="measured")
    assert zero["fractions"]["forward"] is None


# -- peak table --------------------------------------------------------------


def test_peak_table_keys_off_device_kind():
    assert perf.peak_flops("TPU v4") == 275e12
    assert perf.peak_flops("TPU v5 lite") == 197e12
    assert perf.peak_flops("TPU v5p") == 459e12
    assert perf.peak_flops("cpu") == perf.PEAK_TABLE["cpu"][0]
    # an unknown accelerator is an error, never another chip's peak
    with pytest.raises(ValueError, match="not in the peaks table"):
        perf.peak_flops("TPU v9 hypothetical")
    assert perf.peak_bytes_per_sec("TPU v5p") == 2.77e12


def test_peak_env_override_preserved(monkeypatch):
    """SPARKDL_TPU_PEAK_FLOPS must keep its pre-perf.py meaning:
    override the denominator for ANY device kind."""
    monkeypatch.setenv(perf.PEAK_FLOPS_ENV, "123e12")
    assert perf.peak_flops("TPU v4") == 123e12
    assert perf.peak_flops("cpu") == 123e12
    monkeypatch.setenv(perf.PEAK_BYTES_ENV, "1e9")
    assert perf.peak_bytes_per_sec("TPU v4") == 1e9


# -- roofline / MFU gauges ---------------------------------------------------


class _FakeExecutable:
    def __init__(self, flops=2e9, nbytes=1e8, raise_cost=False):
        self._flops, self._bytes = flops, nbytes
        self._raise = raise_cost

    def cost_analysis(self):
        if self._raise:
            raise NotImplementedError("no cost model on this runtime")
        return [{"flops": self._flops, "bytes accessed": self._bytes}]

    def memory_analysis(self):
        class MA:
            temp_size_in_bytes = 4096
            argument_size_in_bytes = 128
            output_size_in_bytes = 64
        return MA()


def _gauge_value(name, **labels):
    snap = observe.metrics().snapshot()
    for g in snap["gauges"]:
        if g["name"] == name and all(
                g["labels"].get(k) == str(v) for k, v in labels.items()):
            return g["value"]
    return None


def test_register_and_note_step_sets_roofline_gauges(monkeypatch,
                                                     tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    monkeypatch.setenv(perf.PEAK_FLOPS_ENV, "1e12")
    monkeypatch.setenv(perf.PEAK_BYTES_ENV, "1e11")
    observe._reset_for_tests()
    entry = perf.register_step_cost("train_step", _FakeExecutable())
    assert entry["flops"] == 2e9
    assert entry["bytes_accessed"] == 1e8
    # the peak denominators resolve ONCE at registration (note_step
    # is hot-path) and honor the env override
    assert entry["peak_flops"] == 1e12
    assert entry["peak_bytes"] == 1e11
    perf.note_step("train_step", 0.01)  # 10ms/step
    assert _gauge_value("step_cost_flops", fn="train_step") == 2e9
    assert _gauge_value(
        "achieved_flops_per_sec", fn="train_step") == pytest.approx(2e11)
    assert _gauge_value("mfu", fn="train_step") == pytest.approx(0.2)
    assert _gauge_value(
        "achieved_bytes_per_sec", fn="train_step") == pytest.approx(1e10)
    assert _gauge_value("membw_util", fn="train_step") == pytest.approx(0.1)
    assert _gauge_value(
        "step_operational_intensity", fn="train_step") == pytest.approx(20.0)


def test_missing_cost_model_means_no_gauges(monkeypatch, tmp_path):
    """A runtime without a cost model degrades to silence: register
    returns None, note_step is a no-op, nothing appears."""
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    assert perf.register_step_cost(
        "train_step", _FakeExecutable(raise_cost=True)) is None
    perf.note_step("train_step", 0.01)
    perf.note_step("never_registered", 0.01)
    snap = observe.metrics().snapshot()
    assert snap["gauges"] == []


def test_note_step_ignores_nonpositive_durations(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    perf.register_step_cost("train_step", _FakeExecutable())
    perf.note_step("train_step", 0.0)
    assert _gauge_value("achieved_flops_per_sec", fn="train_step") is None


def test_zero_overhead_latch_no_perf_state_when_disabled():
    """Telemetry off (the default): cost registration is a no-op that
    allocates nothing — the zero-overhead contract extends to perf."""
    assert not observe.enabled()
    assert perf.register_step_cost("train_step", _FakeExecutable()) is None
    assert perf._step_costs == {}
    perf.note_step("train_step", 0.01)
    assert observe.metrics().snapshot()["gauges"] == []
    assert len(observe.timeline()) == 0


# -- aggregate writes perf.json ----------------------------------------------


def test_gang_telemetry_writes_perf_json(monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    gt = GangTelemetry()
    gt.ingest(0, {"pid": 10, "host": "h", "events": [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("reduce", "collective", 10, 20, tid=1),
    ]})
    out = tmp_path / "run"
    paths = gt.write(str(out))
    assert "perf.json" in paths
    doc = json.loads((out / "perf.json").read_text())
    rep = doc["ranks"]["0"]
    assert rep["steps"] == 1
    assert rep["components"]["collective"] == pytest.approx(0.020)
    assert sum(rep["components"].values()) == pytest.approx(
        rep["total_s"], rel=0.05)


def test_gang_telemetry_skips_perf_json_without_step_spans(
        monkeypatch, tmp_path):
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    gt = GangTelemetry()
    gt.ingest(0, {"pid": 10, "host": "h", "events": [
        span("reduce", "collective", 10, 20, tid=1),
    ]})
    paths = gt.write(str(tmp_path / "run"))
    assert "perf.json" not in paths


# -- doctor: "where the time went" -------------------------------------------


def _perf_run_dir(tmp_path, with_mfu=True):
    from sparkdl_tpu.observe.metrics import Registry

    gt = GangTelemetry()
    reg = Registry()
    if with_mfu:
        reg.gauge("mfu", fn="train_step", device_kind="cpu").set(0.335)
    gt.ingest(0, {"pid": 10, "host": "h", "metrics": reg.snapshot(),
                  "events": [
        span("train_step", "train", 0, 100, tid=1, step=0),
        span("reduce", "collective", 10, 20, tid=1),
        span("data.wait", "data", 40, 5, tid=1),
    ]})
    out = tmp_path / "run-42-0"
    gt.write(str(out))
    return str(out)


def test_doctor_reports_where_the_time_went(monkeypatch, tmp_path):
    from sparkdl_tpu.observe import doctor

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    run = _perf_run_dir(tmp_path)
    diag = doctor.diagnose(run)
    entry = diag["perf"]["0"]
    assert entry["steps"] == 1
    assert entry["fractions"]["collective"] == pytest.approx(0.2)
    assert entry["fractions"]["compute"] == pytest.approx(0.75)
    assert entry["mfu"] == pytest.approx(0.335)
    text = doctor.render_text(diag)
    assert "where the time went" in text
    assert "collective 20.0%" in text
    assert "data wait 5.0%" in text
    assert "MFU 33.50%" in text


def test_doctor_recomputes_breakdown_without_perf_json(monkeypatch,
                                                       tmp_path):
    """A partial run-dir copy that lost perf.json still gets the
    section: the doctor re-derives it from the merged timeline (lane
    r+1 = rank r)."""
    from sparkdl_tpu.observe import doctor

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    run = _perf_run_dir(tmp_path, with_mfu=False)
    os.unlink(os.path.join(run, "perf.json"))
    diag = doctor.diagnose(run)
    entry = diag["perf"]["0"]
    assert entry["fractions"]["collective"] == pytest.approx(0.2)
    assert entry.get("mfu") is None


def test_doctor_no_perf_section_without_step_spans(monkeypatch,
                                                   tmp_path):
    from sparkdl_tpu.observe import doctor

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    gt = GangTelemetry()
    gt.ingest(0, {"pid": 10, "host": "h", "events": [
        span("reduce", "collective", 10, 20, tid=1)]})
    out = tmp_path / "run-43-0"
    gt.write(str(out))
    diag = doctor.diagnose(str(out))
    assert diag["perf"] is None
    assert "where the time went" not in doctor.render_text(diag)


# -- acceptance: the real thing in a 2-rank gang -----------------------------


def _perf_gang_main(n_steps):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.parallel.train import instrument_step, lower_train_step

    hvd.init()

    @jax.jit
    def compute(x):
        return jnp.dot(x, x).sum()

    # registers the executable's analytic FLOPs/bytes under the
    # instrument_step name -> note_step feeds the mfu gauges
    lowered = lower_train_step(compute, jnp.ones((64, 64)))
    lowered.compile()

    def step(x):
        y = float(compute(jnp.asarray(x[0])))
        # a real collective inside the step window: the breakdown's
        # serialized-collective component
        hvd.allreduce(np.full((8,), y, np.float32), op=hvd.Sum)
        return y

    stepped = instrument_step(step)
    for _ in range(n_steps):
        stepped(np.ones((1, 64, 64), np.float32))
    return {"rank": hvd.rank(), "size": hvd.size()}


@pytest.mark.gang
def test_gang_run_dir_carries_breakdown_and_mfu(monkeypatch, tmp_path):
    """ISSUE 7 acceptance: with the telemetry env set, a 2-rank gang's
    artifacts contain a per-step breakdown whose components sum to
    within 5% of step wall time, plus MFU/achieved-FLOPs gauges in
    metrics.prom."""
    import glob

    from sparkdl import HorovodRunner

    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    result = HorovodRunner(np=-2).run(_perf_gang_main, n_steps=3)
    assert result["size"] == 2

    (run,) = glob.glob(str(tmp_path / "run-*"))
    doc = json.loads(open(os.path.join(run, "perf.json")).read())
    assert doc["schema"] == perf.BREAKDOWN_SCHEMA
    for rank in ("0", "1"):
        rep = doc["ranks"][rank]
        assert rep["steps"] >= 2
        # the acceptance sum: step-thread components vs step wall time
        assert sum(rep["components"].values()) == pytest.approx(
            rep["total_s"], rel=0.05)
        assert rep["components"]["collective"] > 0
        # host-threaded barrier collectives: nothing overlapped yet
        assert rep["overlap_efficiency"] == pytest.approx(0.0)
        for row in rep["per_step"]:
            assert sum(row["components"].values()) == pytest.approx(
                row["dur_s"], rel=0.05)

    prom = open(os.path.join(run, "metrics.prom")).read()
    for rank in (0, 1):
        assert (f'achieved_flops_per_sec{{fn="train_step",'
                f'rank="{rank}"}}' in prom)
        assert f'mfu{{device_kind="cpu",fn="train_step",rank="{rank}"}}' \
            in prom


# -- regression ledger -------------------------------------------------------


def test_history_record_schema_and_append_roundtrip(tmp_path,
                                                    monkeypatch):
    monkeypatch.delenv(perf.HISTORY_ENV, raising=False)
    rec = perf.history_record(
        {"tok_s": {"value": 100.0, "unit": "tokens/sec",
                   "samples": [99, 101]},
         "plain": 5.0,
         "skipped": {"value": None}},
        device_kind="cpu", bench="test",
    )
    assert rec["schema"] == perf.HISTORY_SCHEMA
    assert rec["host"] == perf.host_fingerprint()
    assert rec["metrics"]["tok_s"]["samples"] == [99, 101]
    assert rec["metrics"]["plain"] == {"value": 5.0}
    assert "skipped" not in rec["metrics"]
    path = tmp_path / "h.jsonl"
    assert perf.append_history(rec, str(path)) == str(path)
    perf.append_history(rec, str(path))
    entries = perf.read_history(str(path))
    assert len(entries) == 2
    assert entries[0]["metrics"]["tok_s"]["value"] == 100.0


def test_append_history_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv(perf.HISTORY_ENV, "0")
    rec = perf.history_record({"m": 1.0})
    assert perf.append_history(rec, str(tmp_path / "h.jsonl")) is None
    assert not (tmp_path / "h.jsonl").exists()


def test_default_history_path_env_override(monkeypatch, tmp_path):
    monkeypatch.setenv(perf.HISTORY_ENV, str(tmp_path / "custom.jsonl"))
    assert perf.default_history_path() == str(tmp_path / "custom.jsonl")
    monkeypatch.delenv(perf.HISTORY_ENV)
    assert perf.default_history_path().endswith(
        os.path.join("benchmarks", "results", "history.jsonl"))


def test_read_history_skips_garbage_lines(tmp_path):
    p = tmp_path / "h.jsonl"
    p.write_text('{"schema": 1, "metrics": {}}\nnot json\n\n')
    assert len(perf.read_history(str(p))) == 1
