"""Search-core tests with a STUBBED trial runner (ISSUE 12): no jax,
no subprocesses — deterministic fake ledger samples drive the greedy
search, the attribution pruner, the noise-aware judge, and the
proof-or-degrade verification, all tier-1."""

import pytest

from sparkdl_tpu.perf import autotune as at
from sparkdl_tpu.perf import profile as prof
from sparkdl_tpu.utils import knobs as knob_reg

PRIMARY = "tok_s"


def _m(samples):
    """One ledger-shaped metric map from rep samples (median = the
    compared value, like perf.sample_metric)."""
    xs = sorted(samples)
    return {PRIMARY: {"value": xs[len(xs) // 2], "samples": list(samples),
                      "unit": "tok/s", "higher_is_better": True}}


class StubRunner:
    """Deterministic trial runner: a table from knob overrides to fake
    rep samples. Every run is recorded — trial-count assertions read
    ``calls``."""

    bench = "cpu-proxy"
    device_kind = "cpu"

    def __init__(self, table, default, attribution=None,
                 primary=PRIMARY):
        self.table = {frozenset(k.items()): v for k, v in table}
        self.default = default
        self._attribution = attribution
        self.primary_metric = primary
        self.calls = []

    def attribution(self):
        return self._attribution

    def run(self, overrides):
        self.calls.append(dict(overrides))
        key = frozenset({k: str(v) for k, v in overrides.items()}.items())
        return _m(self.table.get(key, self.default))


def _knob(name="SPARKDL_TPU_STUB", values=("1", "2"), component=None,
          default="1", tunable=True):
    return knob_reg.Knob(
        name=name, type="int", default=default, subsystem="test",
        tunable=tunable, trial_values=tuple(values),
        benches=("cpu-proxy",), component=component)


# -- pruning -----------------------------------------------------------------


def test_compute_bound_attribution_prunes_data_knobs():
    """The headline pruning contract: a report showing the step is
    80%+ compute removes data-pipeline knobs from the trial plan —
    prefetch depth is never proposed."""
    prefetch = knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH")
    chunk = _knob("SPARKDL_TPU_LOSS_CHUNK", values=("256", "1024"),
                  default="512")
    space = [(prefetch, list(prefetch.trial_values)),
             (chunk, list(chunk.trial_values))]
    report = {"source": "test", "fractions": {"compute": 0.85,
                                              "data_wait": 0.01}}
    kept, pruned = at.prune_space(space, report)
    assert [kb.name for kb, _ in kept] == ["SPARKDL_TPU_LOSS_CHUNK"]
    assert pruned[0][0] == "SPARKDL_TPU_PREFETCH_DEPTH"
    assert "data_wait" in pruned[0][1]


def test_compute_bound_rule_without_explicit_data_wait_row():
    prefetch = knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH")
    kept, pruned = at.prune_space(
        [(prefetch, ["4"])],
        {"source": "t", "fractions": {"compute": 0.9}})
    assert not kept and pruned


def test_no_attribution_means_no_pruning():
    prefetch = knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH")
    kept, pruned = at.prune_space([(prefetch, ["4"])], None)
    assert kept and not pruned


def test_queue_wait_fraction_prunes_max_queue():
    """The serving twin of the rule: near-zero queue wait never
    explores the admission bound."""
    mq = knob_reg.get("SPARKDL_TPU_SERVE_MAX_QUEUE")
    report = {"source": "serve_bench", "fractions": {"queue_wait": 0.001}}
    kept, pruned = at.prune_space([(mq, ["16", "64"])], report)
    assert not kept
    assert pruned[0][0] == "SPARKDL_TPU_SERVE_MAX_QUEUE"


def test_pruned_knobs_never_reach_the_runner():
    prefetch = knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH")
    chunk = _knob("SPARKDL_TPU_LOSS_CHUNK", values=("1024",),
                  default="512")
    runner = StubRunner(
        [({"SPARKDL_TPU_LOSS_CHUNK": "1024"}, [1100, 1105, 1110, 1102])],
        default=[1000, 1001, 1002, 1003],
        attribution={"source": "t",
                     "fractions": {"compute": 0.95, "data_wait": 0.0}})
    result = at.autotune(
        runner,
        [(prefetch, ["4", "8"]), (chunk, ["1024"])],
        log=lambda *_: None)
    assert all("SPARKDL_TPU_PREFETCH_DEPTH" not in c
               for c in runner.calls)
    assert result.pruned[0][0] == "SPARKDL_TPU_PREFETCH_DEPTH"
    assert result.best_overrides == {"SPARKDL_TPU_LOSS_CHUNK": "1024"}


# -- noise-aware judging -----------------------------------------------------


def test_noisy_but_flat_knob_is_rejected():
    """A candidate whose samples are noisy but whose median is flat
    must NOT be adopted — the IQR threshold rises with the noise, so
    a jittery tie never counts as an improvement."""
    kb = _knob(values=("1", "2"))
    runner = StubRunner(
        # median 1010 (+1%), rel-IQR ~20%: inside the noise band
        [({kb.name: "2"}, [700, 900, 1010, 1100, 1300])],
        default=[980, 1000, 1000, 1010, 1020])
    result = at.autotune(runner, [(kb, ["2"])], log=lambda *_: None)
    assert result.best_overrides == {}
    assert result.trials[0].decision == "ok"


def test_quiet_real_improvement_is_adopted():
    kb = _knob(values=("1", "2"))
    runner = StubRunner(
        [({kb.name: "2"}, [1200, 1205, 1210, 1203, 1207])],
        default=[1000, 1001, 1002, 1003, 1004])
    result = at.autotune(runner, [(kb, ["2"])], log=lambda *_: None)
    assert result.best_overrides == {kb.name: "2"}
    assert result.trials[0].decision == "improved"


def test_greedy_search_composes_overrides_and_bounds_trials():
    """Two knobs, two values each: the plan is 1 baseline + 2
    candidates (default values are never re-measured) — bounded by
    the space size 4 — and knob 2's trial runs ON TOP of knob 1's
    adopted winner."""
    k1 = _knob("SPARKDL_TPU_STUB_A", values=("1", "2"))
    k2 = _knob("SPARKDL_TPU_STUB_B", values=("1", "2"))
    runner = StubRunner(
        [({"SPARKDL_TPU_STUB_A": "2"}, [1200, 1201, 1202, 1203]),
         ({"SPARKDL_TPU_STUB_A": "2", "SPARKDL_TPU_STUB_B": "2"},
          [1500, 1501, 1502, 1503])],
        default=[1000, 1001, 1002, 1003])
    result = at.autotune(runner, [(k1, ["1", "2"]), (k2, ["1", "2"])],
                         log=lambda *_: None)
    assert len(runner.calls) == 3          # baseline + 2 candidates
    assert len(runner.calls) <= result.space_size
    assert runner.calls[2] == {"SPARKDL_TPU_STUB_A": "2",
                               "SPARKDL_TPU_STUB_B": "2"}
    assert result.best_overrides == {"SPARKDL_TPU_STUB_A": "2",
                                     "SPARKDL_TPU_STUB_B": "2"}


def test_max_trials_refuses_loudly_instead_of_truncating():
    kb = _knob(values=("1", "2", "3", "4"))
    runner = StubRunner([], default=[1000, 1001, 1002, 1003])
    with pytest.raises(SystemExit, match="max-trials"):
        at.autotune(runner, [(kb, ["2", "3", "4"])], max_trials=2,
                    log=lambda *_: None)
    assert runner.calls == []              # refused BEFORE measuring


def test_failed_trial_is_recorded_not_fatal():
    kb = _knob(values=("1", "2"))

    class Failing(StubRunner):
        def run(self, overrides):
            if overrides:
                self.calls.append(dict(overrides))
                raise at.TrialError("bench crashed")
            return super().run(overrides)

    runner = Failing([], default=[1000, 1001, 1002, 1003])
    result = at.autotune(runner, [(kb, ["2"])], log=lambda *_: None)
    assert result.best_overrides == {}
    assert result.trials[0].decision == "failed"
    assert "crashed" in result.trials[0].error


# -- proof-or-degrade verification ------------------------------------------


def test_verification_regression_degrades_to_defaults():
    """The search adopts a knob on a lucky trial; the fresh
    verification pair disagrees — the profile must come out DEGRADED
    with no applied knobs, candidate recorded, and the launcher
    pre-flight must apply nothing from it."""
    kb = _knob(values=("1", "2"))

    class Flaky(StubRunner):
        """knob=2 looks +20% during the search, -20% at verification
        (runs 4+ see the regression)."""

        def run(self, overrides):
            n = len(self.calls)
            out = super().run(overrides)
            if overrides and n >= 2:
                out[PRIMARY] = {**out[PRIMARY],
                                "value": 800.0,
                                "samples": [798, 799, 800, 801]}
            return out

    runner = Flaky([({kb.name: "2"}, [1200, 1201, 1202, 1203])],
                   default=[1000, 1001, 1002, 1003])
    result = at.autotune(runner, [(kb, ["2"])], log=lambda *_: None)
    assert result.best_overrides == {kb.name: "2"}
    doc = at.verify_and_emit(runner, result, log=lambda *_: None)
    assert doc["status"] == prof.STATUS_DEGRADED
    assert doc["knobs"] == {}
    assert doc["candidate_knobs"] == {kb.name: "2"}
    assert doc["evidence"]["verification"]["primary"]["status"] == \
        "regression"
    # and the apply side honors the degrade: nothing is exported
    assert prof.profile_env_delta(doc, {}) == {}


def test_secondary_regression_protection_rules():
    """Whole-record verification: a SAMPLE-PROTECTED secondary metric
    regressing degrades the winner; an unprotected single-invocation
    secondary jittering down does NOT (the never-a-single-invocation
    rule applies to the degrade decision too)."""
    kb = knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH")

    def run_factory(secondary_samples):
        class R(StubRunner):
            def run(self, overrides):
                out = super().run(overrides)
                if overrides:   # winner side: secondary drops 10%
                    out["secondary"] = (
                        {"value": 90.0, "samples": secondary_samples,
                         "higher_is_better": True}
                        if secondary_samples else
                        {"value": 90.0, "higher_is_better": True})
                else:
                    out["secondary"] = (
                        {"value": 100.0,
                         "samples": [99.0, 100.0, 100.0, 101.0],
                         "higher_is_better": True}
                        if secondary_samples else
                        {"value": 100.0, "higher_is_better": True})
                return out
        return R([({kb.name: "1024"}, [1200, 1201, 1202, 1203])],
                 default=[1000, 1001, 1002, 1003])

    protected = run_factory([89.0, 90.0, 90.0, 91.0])
    result = at.autotune(protected, [(kb, ["1024"])],
                         log=lambda *_: None)
    doc = at.verify_and_emit(protected, result, log=lambda *_: None)
    assert doc["status"] == prof.STATUS_DEGRADED

    unprotected = run_factory(None)
    result = at.autotune(unprotected, [(kb, ["1024"])],
                         log=lambda *_: None)
    doc = at.verify_and_emit(unprotected, result, log=lambda *_: None)
    assert doc["status"] == prof.STATUS_VERIFIED


def test_verification_pass_emits_verified_profile():
    kb = knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH")
    runner = StubRunner(
        [({kb.name: "1024"}, [1200, 1201, 1202, 1203])],
        default=[1000, 1001, 1002, 1003])
    result = at.autotune(runner, [(kb, ["1024"])], log=lambda *_: None)
    doc = at.verify_and_emit(runner, result, log=lambda *_: None)
    assert doc["status"] == prof.STATUS_VERIFIED
    assert doc["knobs"] == {kb.name: "1024"}
    assert doc["schema"] == prof.PROFILE_SCHEMA
    assert doc["device_kind"] == "cpu"
    # ties/improvements apply
    assert prof.profile_env_delta(doc, {}) == {kb.name: "1024"}


def test_empty_winner_skips_verification_runs():
    kb = _knob(values=("1", "2"))
    runner = StubRunner([], default=[1000, 1001, 1002, 1003])
    result = at.autotune(runner, [(kb, ["2"])], log=lambda *_: None)
    n_before = len(runner.calls)
    doc = at.verify_and_emit(runner, result, log=lambda *_: None)
    assert len(runner.calls) == n_before   # no extra measurements
    assert doc["status"] == prof.STATUS_VERIFIED
    assert doc["knobs"] == {}


# -- space derivation --------------------------------------------------------


def test_derive_space_from_registry():
    space = at.derive_space("gbdt")
    names = {kb.name for kb, _ in space}
    assert "SPARKDL_TPU_GBDT_MAX_BINS" in names
    assert "SPARKDL_TPU_SERVE_QUANT" not in names


def test_derive_space_value_overrides_and_unknown_knob():
    space = at.derive_space(
        "gbdt", knob_names=["SPARKDL_TPU_GBDT_MAX_BINS"],
        value_overrides={"SPARKDL_TPU_GBDT_MAX_BINS": ["64", "256"]})
    assert space == [(knob_reg.get("SPARKDL_TPU_GBDT_MAX_BINS"),
                      ["64", "256"])]
    with pytest.raises(SystemExit, match="not a registered tunable"):
        at.derive_space("gbdt", knob_names=["SPARKDL_TPU_RANK"])


def test_values_matching_no_space_knob_refuse_loudly():
    """A typo'd --values must not silently measure the declared
    space instead of the requested one."""
    with pytest.raises(SystemExit, match="match no knob"):
        at.derive_space(
            "gbdt",
            value_overrides={"SPARKDL_TPU_GBDT_MAX_BINZ": ["64"]})


def test_trial_ledger_readback_filters_by_bench_tag(tmp_path):
    """A concurrent writer's ledger line must never be attributed to
    the trial: run() only accepts NEW entries carrying this harness's
    bench tag, and raises a TrialError otherwise."""
    from sparkdl_tpu.observe import perf as operf

    history = tmp_path / "history.jsonl"

    class FakeBenchRunner(at.SubprocessTrialRunner):
        bench = "cpu-proxy"
        ledger_bench = "fake_bench"

        def command(self):
            return ["true"]

        def _bounded_run(self, args, env):
            # simulate: a FOREIGN bench appends during our trial
            operf.append_history(
                operf.history_record({"other": 1.0},
                                     bench="serve_bench"),
                str(history))
            return 0, "", ""

    runner = FakeBenchRunner(history_path=str(history))
    with pytest.raises(at.TrialError, match="bench='fake_bench'"):
        runner.run({})
    # and a correctly-tagged line IS picked up, even with the foreign
    # one interleaved after it
    class GoodRunner(FakeBenchRunner):
        def _bounded_run(self, args, env):
            operf.append_history(
                operf.history_record({PRIMARY: 10.0}, bench="fake_bench",
                                     device_kind="cpu"), str(history))
            operf.append_history(
                operf.history_record({"other": 1.0},
                                     bench="serve_bench"),
                str(history))
            return 0, "", ""

    good = GoodRunner(history_path=str(history))
    metrics = good.run({})
    assert metrics[PRIMARY]["value"] == 10.0
    assert good.primary_metric == PRIMARY


def test_trial_timeout_is_a_failed_trial_not_a_crash(tmp_path):
    runner = at.GbdtRunner(history_path=str(tmp_path / "h.jsonl"),
                           timeout=0.3)
    runner.command = lambda: [
        "python", "-c", "import time; time.sleep(30)"]
    with pytest.raises(at.TrialError, match="timed out"):
        runner.run({})


def test_attention_runner_static_attribution_is_compute_bound():
    """The attention harness declares (not measures) that its program
    is one jitted kernel scan: the pruner must see a compute-bound
    report."""
    r = at.AttentionRunner(history_path="/dev/null")
    rep = r.attribution()
    assert rep["fractions"]["compute"] >= at.COMPUTE_BOUND_FRACTION
    kept, pruned = at.prune_space(
        [(knob_reg.get("SPARKDL_TPU_PREFETCH_DEPTH"), ["4"])], rep)
    assert not kept and pruned
