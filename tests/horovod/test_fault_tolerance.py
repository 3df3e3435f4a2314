"""Fault-injection proof of the gang supervisor (ISSUE: preemption-
aware gang supervision).

The chaos harness (:mod:`sparkdl_tpu.utils.chaos`) injects the
failures real pods hit — a rank SIGKILLed mid-step (preemption), a
worker dead before rendezvous, READY frames dropped on the control
plane — and these tests prove the supervisor's contract end to end
on CPU gangs:

1. a gang whose rank is killed mid-step relaunches under backoff,
   resumes from the latest checkpoint, and produces final parameters
   IDENTICAL to an uninterrupted run;
2. a user-code exception is never retried (attempt count == 1);
3. retry-budget exhaustion raises a typed error naming every attempt
   with its classified cause.

Unit-level classification/backoff/codec checks ride along so the
classification itself is pinned without spawning gangs.
"""

import os
import signal

import pytest

from sparkdl import HorovodRunner
from sparkdl_tpu.horovod.supervisor import (
    PERMANENT,
    TRANSIENT,
    AttemptRecord,
    GangFailure,
    GangRetryBudgetExhausted,
    RetryPolicy,
    classify_failure,
    supervise,
)

pytestmark = pytest.mark.chaos


# -- failure classification (no gangs spawned) ------------------------------


def test_signal_death_is_transient():
    verdict, cause = classify_failure(
        GangFailure("gang died", kind="worker_death",
                    exit_codes=[0, -signal.SIGKILL])
    )
    assert verdict == TRANSIENT
    assert "sig 9" in cause


def test_user_exception_is_permanent_even_with_killed_survivors():
    # The failing rank raised; the grace-period abort then SIGKILLed
    # the survivors — the user traceback must dominate the signal
    # deaths or every user bug would be retried.
    tb = ("Traceback (most recent call last):\n"
          "  ...\nValueError: bad hyperparameter")
    verdict, cause = classify_failure(
        GangFailure("gang died", kind="worker_death",
                    exit_codes=[1, -signal.SIGKILL], exceptions={0: tb})
    )
    assert verdict == PERMANENT
    assert "rank(s) [0]" in cause


def test_infra_exception_is_transient():
    # A rank observing its peer's preemption raises a connection error
    # of its own; that traceback must not veto the retry.
    tb = ("Traceback (most recent call last):\n  ...\n"
          "jaxlib.xla_extension.XlaRuntimeError: UNKNOWN: Gloo "
          "allreduce failed: Connection closed by peer [127.0.0.1]")
    verdict, _ = classify_failure(
        GangFailure("gang died", kind="worker_death",
                    exit_codes=[1, -signal.SIGKILL], exceptions={0: tb})
    )
    assert verdict == TRANSIENT


def test_infra_vocabulary_in_user_frames_stays_permanent():
    # A user traceback whose FILE PATHS and source lines mention
    # infrastructure vocabulary must still classify as user code: the
    # signature match reads only the terminal exception block.
    tb = ('Traceback (most recent call last):\n'
          '  File "/home/u/gloo_utils.py", line 9, in rendezvous_data\n'
          '    raise ValueError("bad shard spec")\n'
          'ValueError: bad shard spec')
    verdict, cause = classify_failure(
        GangFailure("gang died", kind="worker_death",
                    exit_codes=[1, 0], exceptions={0: tb}))
    assert verdict == PERMANENT
    assert "rank(s) [0]" in cause


def test_rendezvous_timeout_and_lost_result_are_transient():
    assert classify_failure(
        GangFailure("x", kind="rendezvous_timeout"))[0] == TRANSIENT
    assert classify_failure(
        GangFailure("x", kind="no_result"))[0] == TRANSIENT


def test_port_clash_is_transient():
    tb = ("Traceback (most recent call last):\n  ...\n"
          "RuntimeError: Failed to initialize coordinator: "
          "Address already in use")
    assert classify_failure(
        GangFailure("x", kind="start_failure", exit_codes=[1, 0],
                    exceptions={0: tb}))[0] == TRANSIENT


def test_slot_and_argument_errors_are_permanent():
    from sparkdl_tpu.horovod.launcher import (
        SlotExhaustionError,
        SlotProbeError,
        SlotWaitTimeout,
    )

    for exc in (SlotExhaustionError("np too big"),
                SlotProbeError("probe died"),
                SlotWaitTimeout("gave up"),
                ValueError("per_rank_kwargs mismatch")):
        assert classify_failure(exc)[0] == PERMANENT


def test_unclassified_worker_exit_is_permanent():
    # exit 1 with no traceback (e.g. an import error at bootstrap):
    # retrying what we cannot name would hide real breakage.
    verdict, cause = classify_failure(
        GangFailure("x", kind="worker_death", exit_codes=[1, 0]))
    assert verdict == PERMANENT
    assert "not retried blindly" in cause


def test_operator_extends_transient_patterns(monkeypatch):
    tb = "FrobnicationError: ICI link flapped on chip 3"
    gf = GangFailure("x", kind="worker_death", exit_codes=[1],
                     exceptions={0: tb})
    assert classify_failure(gf)[0] == PERMANENT
    monkeypatch.setenv("SPARKDL_TPU_TRANSIENT_PATTERNS",
                       "ici link flapped; other signature")
    assert classify_failure(gf)[0] == TRANSIENT


def test_backoff_schedule_is_capped_exponential_with_jitter():
    p = RetryPolicy(max_retries=5, backoff_base=1.0, backoff_factor=2.0,
                    backoff_max=5.0, jitter=0.5)
    assert p.backoff(1, _random=lambda: 0.0) == 1.0
    assert p.backoff(3, _random=lambda: 0.0) == 4.0
    assert p.backoff(4, _random=lambda: 0.0) == 5.0   # capped
    assert p.backoff(1, _random=lambda: 1.0) == 1.5   # +jitter bound


def test_policy_env_and_legacy_alias(monkeypatch):
    monkeypatch.delenv("SPARKDL_TPU_GANG_MAX_RETRIES", raising=False)
    monkeypatch.setenv("SPARKDL_TPU_MAX_RESTARTS", "3")
    assert RetryPolicy.from_env().max_retries == 3
    monkeypatch.setenv("SPARKDL_TPU_GANG_MAX_RETRIES", "7")
    monkeypatch.setenv("SPARKDL_TPU_GANG_RESUME_DIR", "/ckpt")
    p = RetryPolicy.from_env()
    assert p.max_retries == 7 and p.resume_dir == "/ckpt"


def test_supervise_ships_restart_context(tmp_path):
    # Two committed steps + one uncommitted orbax temp dir: the
    # relaunch must ship attempt=1 and the newest COMMITTED step.
    (tmp_path / "3").mkdir()
    (tmp_path / "7").mkdir()
    (tmp_path / "9.orbax-checkpoint-tmp-123").mkdir()
    seen = []

    def launch(extra_env):
        seen.append(dict(extra_env))
        if len(seen) == 1:
            raise GangFailure("preempted", kind="worker_death",
                              exit_codes=[-signal.SIGKILL])
        return "done"

    policy = RetryPolicy(max_retries=2, backoff_base=0.0, jitter=0.0,
                         resume_dir=str(tmp_path))
    assert supervise(launch, policy, _sleep=lambda s: None) == "done"
    assert seen[0] == {}  # first attempt: unmodified env
    assert seen[1] == {"SPARKDL_TPU_RESTART_ATTEMPT": "1",
                       "SPARKDL_TPU_RESUME_STEP": "7"}


def test_latest_complete_step_scan(tmp_path):
    from sparkdl_tpu.utils.checkpoint import latest_complete_step

    assert latest_complete_step(tmp_path / "missing") is None
    assert latest_complete_step(tmp_path) is None
    (tmp_path / "0").mkdir()
    (tmp_path / "12").mkdir()
    (tmp_path / "20.orbax-checkpoint-tmp-9").mkdir()  # uncommitted
    (tmp_path / "notes.txt").write_text("x")
    assert latest_complete_step(tmp_path) == 12


def test_chaos_frame_fate_and_once_claim(tmp_path, monkeypatch):
    from sparkdl_tpu.utils import chaos

    monkeypatch.setenv("SPARKDL_TPU_CHAOS_CP_DROP", "ready, result")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_CP_DELAY_S", "0.25")
    chaos._reset_cache_for_tests()
    try:
        assert chaos.control_frame_fate("READY") == "drop"
        assert chaos.control_frame_fate("RESULT") == "drop"
        assert chaos.control_frame_fate("BYE") == 0.25
        once = tmp_path / "token"
        monkeypatch.setenv("SPARKDL_TPU_CHAOS_ONCE_FILE", str(once))
        assert chaos._claim_once() is True    # first claimant wins
        assert once.exists()
        assert chaos._claim_once() is False   # second attempt: no kill
    finally:
        chaos._reset_cache_for_tests()


# -- end-to-end gang proofs -------------------------------------------------


def _ckpt_train_main(ckpt_dir, total_steps):
    """Deterministic checkpointed training loop: resumable via the
    supervisor's restart context. The 'gradient' depends on (rank,
    step), so a skipped or double-applied step changes the result."""
    import numpy as np

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.horovod import restart_context
    from sparkdl_tpu.utils.chaos import chaos_step
    from sparkdl_tpu.utils.checkpoint import TrainCheckpointer

    hvd.init()
    ctx = restart_context()
    ckpt = TrainCheckpointer(ckpt_dir)
    w = np.zeros((4,), np.float32)
    start = 0
    if ctx.resume_step is not None:
        restored = ckpt.restore(
            ctx.resume_step, target={"w": np.zeros((4,), np.float32)})
        w = np.asarray(restored["w"])
        start = ctx.resume_step + 1
    try:
        for step in range(start, total_steps):
            g = hvd.allreduce(
                np.full((4,), float((hvd.rank() + 1) * (step + 1)),
                        np.float32),
                op=hvd.Sum)
            w = (w - 0.01 * np.asarray(g)).astype(np.float32)
            ckpt.save(step, {"w": w})
            ckpt.wait_until_finished()
            hvd.barrier()       # rank 0's save durable before any death
            chaos_step(step)
    finally:
        ckpt.close()
    return {"w": w.tolist(), "attempt": ctx.attempt,
            "resume_step": ctx.resume_step}


@pytest.mark.gang
@pytest.mark.slow
def test_midstep_kill_resumes_and_matches_uninterrupted_run(
        monkeypatch, tmp_path):
    """The acceptance proof: rank 1 is SIGKILLed at step 2 (first
    attempt only); the supervisor relaunches, the main resumes from
    the latest checkpoint, and the final parameters are IDENTICAL to
    an uninterrupted run."""
    steps = 5

    # Uninterrupted reference run (no chaos env yet).
    baseline = HorovodRunner(np=-2).run(
        _ckpt_train_main, ckpt_dir=str(tmp_path / "ref"),
        total_steps=steps)
    assert baseline["attempt"] == 0 and baseline["resume_step"] is None

    monkeypatch.setenv("SPARKDL_TPU_GANG_MAX_RETRIES", "2")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_BASE", "0.1")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_MAX", "0.2")
    monkeypatch.setenv("SPARKDL_TPU_GANG_RESUME_DIR",
                       str(tmp_path / "ck"))
    monkeypatch.setenv("SPARKDL_TPU_ABORT_GRACE", "5")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_KILL_RANK", "1")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_KILL_STEP", "2")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_ONCE_FILE",
                       str(tmp_path / "one-kill"))

    result = HorovodRunner(np=-2).run(
        _ckpt_train_main, ckpt_dir=str(tmp_path / "ck"),
        total_steps=steps)

    assert (tmp_path / "one-kill").exists()      # the kill really fired
    assert result["attempt"] == 1                # exactly one relaunch
    assert result["resume_step"] == 2            # from the latest ckpt
    assert result["w"] == baseline["w"]          # bit-identical params


def _counting_main(marker_path, explode):
    import sparkdl_tpu.hvd as hvd

    hvd.init()
    if hvd.rank() == 0:
        with open(marker_path, "a") as fh:
            fh.write("x")
        if explode:
            raise ValueError("user bug, never worth a relaunch")
    return "ok"


@pytest.mark.gang
@pytest.mark.slow
def test_user_exception_is_never_retried(monkeypatch, tmp_path):
    """A user-code exception must surface after exactly ONE attempt,
    retry budget notwithstanding."""
    monkeypatch.setenv("SPARKDL_TPU_GANG_MAX_RETRIES", "3")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_BASE", "0.1")
    monkeypatch.setenv("SPARKDL_TPU_ABORT_GRACE", "5")
    marker = tmp_path / "attempts"
    with pytest.raises(RuntimeError, match="user bug"):
        HorovodRunner(np=-2).run(
            _counting_main, marker_path=str(marker), explode=True)
    assert marker.read_text() == "x"  # attempt count == 1


def _boot_doomed_main():
    return "unreachable"  # chaos kills the rank before rendezvous


@pytest.mark.gang
@pytest.mark.slow
def test_retry_budget_exhausts_loudly(monkeypatch, tmp_path):
    """Every attempt is killed at boot (no once-token): the budget
    must exhaust with a typed error naming every attempt and its
    classified cause."""
    monkeypatch.setenv("SPARKDL_TPU_GANG_MAX_RETRIES", "2")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_BASE", "0.1")
    monkeypatch.setenv("SPARKDL_TPU_GANG_BACKOFF_MAX", "0.2")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_KILL_RANK", "1")
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_KILL_PHASE", "boot")
    with pytest.raises(GangRetryBudgetExhausted) as e:
        HorovodRunner(np=-2).run(_boot_doomed_main)
    msg = str(e.value)
    assert "retry budget (2" in msg
    assert len(e.value.attempts) == 3
    for n, record in enumerate(e.value.attempts, start=1):
        assert isinstance(record, AttemptRecord)
        assert record.number == n
        assert record.verdict == TRANSIENT
        assert f"attempt {n}: transient" in msg
        assert "sig 9" in record.cause  # the classified cause, named


@pytest.mark.gang
@pytest.mark.slow
def test_dropped_ready_frames_surface_as_rendezvous_timeout(monkeypatch):
    """Control-plane chaos: dropping every READY frame stalls the gang
    barrier; the launcher must time out with a failure that CLASSIFIES
    transient (a relaunch gets fresh connections)."""
    monkeypatch.setenv("SPARKDL_TPU_CHAOS_CP_DROP", "READY")
    monkeypatch.setenv("SPARKDL_TPU_START_TIMEOUT", "8")
    with pytest.raises(GangFailure) as e:
        HorovodRunner(np=-2).run(_counting_main, marker_path=os.devnull,
                                 explode=False)
    assert e.value.kind == "rendezvous_timeout"
    assert classify_failure(e.value)[0] == TRANSIENT
