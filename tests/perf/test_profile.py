"""Profile storage + the launcher pre-flight apply path (ISSUE 12):
round-trip, operator precedence, degrade honoring, resolution keyed by
device kind, and — the acceptance pin — the profile surviving a
supervised gang relaunch through the worker-env forwarding path."""

import json
import os

import pytest

from sparkdl_tpu.perf import profile as prof

KNOB = "SPARKDL_TPU_PREFETCH_DEPTH"


def _verified(tmp_path, knobs=None, **kw):
    doc = prof.make_profile(
        knobs if knobs is not None else {KNOB: "1024"},
        device_kind="cpu", bench="cpu-proxy",
        status=prof.STATUS_VERIFIED, **kw)
    return doc, prof.save_profile(doc, str(tmp_path / "cpu.json"))


def test_profile_round_trip(tmp_path):
    doc, path = _verified(tmp_path, evidence={"trials": []})
    loaded = prof.load_profile(path)
    assert loaded["schema"] == prof.PROFILE_SCHEMA
    assert loaded["knobs"] == {KNOB: "1024"}
    assert loaded["host"] and loaded["device_kind"] == "cpu"


def test_make_profile_refuses_non_tunable_knobs():
    with pytest.raises(prof.ProfileError, match="tunable"):
        prof.make_profile({"SPARKDL_TPU_CONTROL_SECRET": "x"},
                          device_kind="cpu", bench="cpu-proxy",
                          status=prof.STATUS_VERIFIED)
    with pytest.raises(prof.ProfileError, match="tunable"):
        prof.make_profile({"TOTALLY_UNKNOWN": "1"}, device_kind="cpu",
                          bench="cpu-proxy",
                          status=prof.STATUS_VERIFIED)


def test_load_profile_rejects_wrong_schema(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "something/else", "knobs": {}}))
    with pytest.raises(prof.ProfileError, match="schema"):
        prof.load_profile(str(p))


def test_operator_env_wins_over_profile(tmp_path):
    doc, _ = _verified(tmp_path)
    assert prof.profile_env_delta(doc, {}) == {KNOB: "1024"}
    # the operator already pinned the knob: the profile yields
    assert prof.profile_env_delta(doc, {KNOB: "256"}) == {}


def test_unregistered_profile_knob_is_skipped_not_exported(tmp_path):
    doc, path = _verified(tmp_path)
    # simulate a hand-edited profile smuggling an arbitrary env var
    doc["knobs"]["LD_PRELOAD_ISH"] = "evil"
    assert prof.profile_env_delta(doc, {}) == {KNOB: "1024"}


def test_find_profiles_resolution(tmp_path, monkeypatch):
    doc, path = _verified(tmp_path)
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    # explicit file
    monkeypatch.setenv(prof.PROFILE_ENV, path)
    found = prof.find_profiles()
    assert [p for _, p in found] == [path]
    # directory: legacy flat <root>/cpu.json still honored
    monkeypatch.setenv(prof.PROFILE_ENV, str(tmp_path))
    found = prof.find_profiles()
    assert found and found[0][0]["knobs"] == {KNOB: "1024"}
    # disabled
    monkeypatch.setenv(prof.PROFILE_ENV, "off")
    assert prof.find_profiles() == []
    # an explicit path that exists as NEITHER file nor dir is loud —
    # the operator pinned a profile, running without it must not be
    # silent (preflight_env logs it and degrades to defaults)
    monkeypatch.setenv(prof.PROFILE_ENV, str(tmp_path / "cpu.jsn"))
    with pytest.raises(prof.ProfileError, match="neither"):
        prof.find_profiles()
    assert prof.preflight_env() == {}


def test_per_bench_profiles_compose_under_one_kind(tmp_path,
                                                   monkeypatch):
    """Benches tune disjoint knob subsets: a kind's per-bench
    profiles (profiles/<kind>/<bench>.json) all apply; a conflicting
    knob keeps the first profile's value, logged."""
    train = prof.make_profile({KNOB: "1024"}, device_kind="cpu",
                              bench="cpu-proxy",
                              status=prof.STATUS_VERIFIED)
    gbdt = prof.make_profile(
        {"SPARKDL_TPU_GBDT_MAX_BINS": "64", KNOB: "256"},
        device_kind="cpu", bench="gbdt",
        status=prof.STATUS_VERIFIED)
    p1 = prof.save_profile(
        train, prof.profile_path("cpu", "cpu-proxy", root=str(tmp_path)))
    prof.save_profile(
        gbdt, prof.profile_path("cpu", "gbdt", root=str(tmp_path)))
    assert p1 == str(tmp_path / "cpu" / "cpu-proxy.json")
    monkeypatch.setenv(prof.PROFILE_ENV, str(tmp_path))
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    monkeypatch.delenv(KNOB, raising=False)
    monkeypatch.delenv("SPARKDL_TPU_GBDT_MAX_BINS", raising=False)
    assert prof.preflight_env() == {
        KNOB: "1024",                      # cpu-proxy.json sorts first
        "SPARKDL_TPU_GBDT_MAX_BINS": "64",
    }


def test_rotten_profile_is_quarantined_to_itself(tmp_path,
                                                  monkeypatch):
    """One malformed committed profile must not stop the kind's OTHER
    profiles from applying."""
    good = prof.make_profile({KNOB: "1024"}, device_kind="cpu",
                             bench="cpu-proxy",
                             status=prof.STATUS_VERIFIED)
    prof.save_profile(
        good, prof.profile_path("cpu", "cpu-proxy", root=str(tmp_path)))
    (tmp_path / "cpu" / "gbdt.json").write_text("{truncated")
    monkeypatch.setenv(prof.PROFILE_ENV, str(tmp_path))
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    monkeypatch.delenv(KNOB, raising=False)
    assert prof.preflight_env() == {KNOB: "1024"}


def test_strict_device_kind_never_guesses(monkeypatch, tmp_path):
    """A bare `tpu` pin (or an unknown kind string) must resolve to
    NO profile — the old normalize fallback would have guessed v5e
    and shipped another chip's knobs."""
    assert prof.strict_device_kind("TPU v5 lite") == "v5e"
    assert prof.strict_device_kind("TPU v4") == "v4"
    assert prof.strict_device_kind("tpu") is None
    assert prof.strict_device_kind(None) is None
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "tpu")
    monkeypatch.setenv(prof.PROFILE_ENV, str(tmp_path))
    assert prof.find_profiles() == []
    with pytest.raises(prof.ProfileError, match="cannot key"):
        prof.profile_path("tpu", "cpu-proxy")


def test_preflight_env_applies_and_never_raises(tmp_path, monkeypatch):
    doc, path = _verified(tmp_path)
    monkeypatch.setenv(prof.PROFILE_ENV, path)
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    monkeypatch.delenv(KNOB, raising=False)
    assert prof.preflight_env() == {KNOB: "1024"}
    # malformed committed profile: logged, defaults, no exception
    (tmp_path / "cpu.json").write_text("{not json")
    assert prof.preflight_env() == {}


def test_degraded_profile_applies_nothing(tmp_path, monkeypatch):
    doc = prof.make_profile(
        {}, device_kind="cpu", bench="cpu-proxy",
        status=prof.STATUS_DEGRADED, candidate_knobs={KNOB: "1024"})
    path = prof.save_profile(doc, str(tmp_path / "cpu.json"))
    monkeypatch.setenv(prof.PROFILE_ENV, path)
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    assert prof.preflight_env() == {}


# -- launcher + supervisor integration --------------------------------------


def _worker_env_with_profile(extra_env):
    """Exactly the composition _launch_gang_once performs per attempt:
    profile pre-flight under the operator env, then the worker env,
    then the supervisor's restart context on top."""
    from sparkdl_tpu.horovod.launcher import _worker_env

    profile_env = prof.preflight_env(os.environ)
    env = _worker_env(
        os.environ, rank=0, size=1, coordinator="127.0.0.1:1",
        control_addr="127.0.0.1:2", control_secret="s",
        payload_path="/tmp/p", job_dir="/tmp/j", platform="cpu")
    for k, v in profile_env.items():
        env.setdefault(k, v)
    if extra_env:
        env.update(extra_env)
    return env


def test_profile_survives_supervised_relaunch(tmp_path, monkeypatch):
    """Env-inheritance pin (acceptance): attempt 1 and the relaunched
    attempt 2 both carry the profile knob — the pre-flight runs inside
    the launch function the supervisor retries, alongside the restart
    context."""
    from sparkdl_tpu.horovod.supervisor import (
        GangFailure,
        RetryPolicy,
        supervise,
    )

    doc, path = _verified(tmp_path)
    monkeypatch.setenv(prof.PROFILE_ENV, path)
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    monkeypatch.delenv(KNOB, raising=False)

    seen = []

    def launch(extra_env):
        env = _worker_env_with_profile(extra_env)
        seen.append(env)
        if len(seen) == 1:
            raise GangFailure("transient boom",
                              kind="rendezvous_timeout")
        return "ok"

    policy = RetryPolicy(max_retries=2, backoff_base=0.0,
                         backoff_max=0.0, jitter=0.0)
    assert supervise(launch, policy, _sleep=lambda s: None) == "ok"
    assert len(seen) == 2
    for env in seen:
        assert env[KNOB] == "1024"
    # the restart context rides the SAME forwarding path, on top
    assert seen[1]["SPARKDL_TPU_RESTART_ATTEMPT"] == "1"


def test_tile_profile_survives_supervised_relaunch(tmp_path,
                                                   monkeypatch):
    """ISSUE 19 acceptance: a kernel TILE profile — the autotuned
    flash block committed under profiles/<kind>/attention.json — rides
    the same pre-flight path and survives a supervised gang relaunch,
    so retuned tiles outlive preemption exactly like training knobs."""
    from sparkdl_tpu.horovod.supervisor import (
        GangFailure,
        RetryPolicy,
        supervise,
    )

    tile = "SPARKDL_TPU_FLASH_BLOCK_Q"
    doc = prof.make_profile(
        {tile: "256"}, device_kind="cpu", bench="attention",
        status=prof.STATUS_VERIFIED)
    prof.save_profile(
        doc, prof.profile_path("cpu", "attention", root=str(tmp_path)))
    monkeypatch.setenv(prof.PROFILE_ENV, str(tmp_path))
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    monkeypatch.delenv(tile, raising=False)

    seen = []

    def launch(extra_env):
        env = _worker_env_with_profile(extra_env)
        seen.append(env)
        if len(seen) == 1:
            raise GangFailure("transient boom",
                              kind="rendezvous_timeout")
        return "ok"

    policy = RetryPolicy(max_retries=2, backoff_base=0.0,
                         backoff_max=0.0, jitter=0.0)
    assert supervise(launch, policy, _sleep=lambda s: None) == "ok"
    assert len(seen) == 2
    for env in seen:
        assert env[tile] == "256"


def test_operator_pin_survives_relaunch_over_profile(tmp_path,
                                                     monkeypatch):
    doc, path = _verified(tmp_path)
    monkeypatch.setenv(prof.PROFILE_ENV, path)
    monkeypatch.setenv("SPARKDL_TPU_WORKER_PLATFORM", "cpu")
    monkeypatch.setenv(KNOB, "128")     # operator pins the knob
    env = _worker_env_with_profile({})
    assert env[KNOB] == "128"


def _env_probe_main(knob):
    import os

    import sparkdl_tpu.hvd as hvd

    hvd.init()
    return os.environ.get(knob)


@pytest.mark.gang
def test_profile_reaches_real_gang_workers(tmp_path, monkeypatch):
    """End-to-end: a committed-style profile's knob is visible in a
    REAL launched worker's os.environ — the pre-flight applies through
    the actual spawn path, not just the helper."""
    from sparkdl import HorovodRunner

    doc, path = _verified(tmp_path)
    monkeypatch.setenv(prof.PROFILE_ENV, path)
    monkeypatch.delenv(KNOB, raising=False)
    assert HorovodRunner(np=-2).run(_env_probe_main, knob=KNOB) == "1024"
