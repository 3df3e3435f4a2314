"""Gang supervision: preemption-aware retry with checkpoint resume.

The launcher's gangs are fail-fast by design (reference
``runner_base.py:54-58``): any rank dying kills the whole job. Real
TPU pods, however, get preempted, lose hosts, and hit transient
rendezvous failures — and for those, throwing the run away is the
wrong answer when :class:`~sparkdl_tpu.utils.checkpoint.
TrainCheckpointer` already persists every step. This module wraps
``_launch_gang_once`` with the production recovery loop (the spirit of
Horovod's elastic mode, Sergeev & Del Balso 2018, restricted to
gang-relaunch granularity — one jax world per attempt, no membership
changes mid-run):

1. **Classify** each failure as *transient* (worker SIGKILL/
   preemption, rendezvous timeout, control-plane connection reset,
   port clash) or *permanent* (user-code exception, slot exhaustion,
   bad arguments). Permanent failures surface immediately — more
   restarts cannot create slots or fix user code.
2. **Relaunch** transient failures under exponential backoff with
   jitter (thundering-herd safety when many drivers share a
   control plane), up to a retry budget.
3. **Resume**: each relaunch ships a restart context to the workers —
   attempt number and, when a checkpoint directory is configured, the
   latest committed :class:`TrainCheckpointer` step — via env vars
   read by :func:`sparkdl_tpu.horovod.restart_context`. Unmodified
   mains keep working (the context is additive); checkpoint-aware
   mains restart where they left off.
4. **Exhaust loudly**: when the budget runs out,
   :class:`GangRetryBudgetExhausted` names every attempt with its
   classified cause — nothing is swallowed.

Knobs (all env-driven so ``HorovodRunner.run``'s locked signature is
untouched; see ``docs/fault_tolerance.rst``):

- ``SPARKDL_TPU_GANG_MAX_RETRIES``: relaunch budget for transient
  failures (default 0 — supervision off; ``SPARKDL_TPU_MAX_RESTARTS``
  is honored as a legacy alias).
- ``SPARKDL_TPU_GANG_BACKOFF_BASE`` / ``..._FACTOR`` / ``..._MAX``:
  exponential backoff schedule in seconds (defaults 1 / 2 / 60).
- ``SPARKDL_TPU_GANG_BACKOFF_JITTER``: jitter fraction added on top
  of each delay (default 0.5 — up to +50%).
- ``SPARKDL_TPU_GANG_RESUME_DIR``: TrainCheckpointer root whose
  latest committed step is shipped as the resume point.
- ``SPARKDL_TPU_TRANSIENT_PATTERNS``: ``;``-separated extra
  signatures (case-insensitive substring match against worker
  tracebacks) an operator can add for an interconnect whose
  infrastructure errors this module does not know yet.
- ``SPARKDL_TPU_GANG_RELAUNCH_NP``: target world size for the next
  relaunch (the elastic-shrink knob — a preempted pod coming back
  smaller). Before any relaunch with this set, the supervisor runs
  the static reshard pre-flight
  (:func:`sparkdl_tpu.analysis.comms.check_relaunch_np`) against the
  sharding tree the driver registered via
  :func:`sparkdl_tpu.analysis.register_gang_sharding`: an infeasible
  target — indivisible param dim, fractional-host mesh, restore
  high-water over the HBM budget — raises a typed
  :class:`~sparkdl_tpu.analysis.comms.ReshardPreflightError` naming
  the failing param/axis *before* the backoff sleep, instead of an
  OOM (or a sharding crash) mid-restore on the chips. Feasible
  targets are shipped to the relaunched workers through the same env
  var. With no registered tree the relaunch proceeds unchecked
  (nothing provable).
- ``JAX_COMPILATION_CACHE_DIR`` (read by JAX and the worker, not
  here, but load-bearing for this loop): the warm-start compile cache
  (:mod:`sparkdl_tpu.parallel.compile`). It rides the inherited
  environment into every relaunched attempt, so a replacement rank
  deserializes its step executable instead of re-paying the XLA
  compile — the difference between a resume measured in seconds and
  one measured in minutes at Llama scale.
"""

import dataclasses
import json
import logging
import os
import random
import re
import time

logger = logging.getLogger("HorovodRunner")

GANG_MAX_RETRIES_ENV = "SPARKDL_TPU_GANG_MAX_RETRIES"
LEGACY_MAX_RESTARTS_ENV = "SPARKDL_TPU_MAX_RESTARTS"
BACKOFF_BASE_ENV = "SPARKDL_TPU_GANG_BACKOFF_BASE"
BACKOFF_FACTOR_ENV = "SPARKDL_TPU_GANG_BACKOFF_FACTOR"
BACKOFF_MAX_ENV = "SPARKDL_TPU_GANG_BACKOFF_MAX"
BACKOFF_JITTER_ENV = "SPARKDL_TPU_GANG_BACKOFF_JITTER"
RESUME_DIR_ENV = "SPARKDL_TPU_GANG_RESUME_DIR"
EXTRA_PATTERNS_ENV = "SPARKDL_TPU_TRANSIENT_PATTERNS"
# Elastic-relaunch target np. Same literal as
# sparkdl_tpu.analysis.comms.RELAUNCH_NP_ENV (kept as a plain string
# here so this module never imports the analysis package at import
# time); tests pin the two spellings together.
RELAUNCH_NP_ENV = "SPARKDL_TPU_GANG_RELAUNCH_NP"

# The restart context workers read back via
# sparkdl_tpu.horovod.restart_context(). Shipped per-attempt through
# the worker env (never mutated in the driver's own os.environ — two
# concurrent supervised gangs in one driver must not see each other's
# attempt counters).
RESTART_ATTEMPT_ENV = "SPARKDL_TPU_RESTART_ATTEMPT"
RESUME_STEP_ENV = "SPARKDL_TPU_RESUME_STEP"
# Elastic relaunch mesh contract (JSON axis-size dicts): the recorded
# source mesh axes of the resume checkpoint and the target axes
# shrink_mesh derived for RELAUNCH_NP — shipped so relaunched worker
# mains rebuild the shrunken (or regrown) mesh without guessing.
RESHARD_SOURCE_AXES_ENV = "SPARKDL_TPU_RESHARD_SOURCE_AXES"
RESHARD_TARGET_AXES_ENV = "SPARKDL_TPU_RESHARD_TARGET_AXES"

# World size of every launch attempt in this driver process, in order
# (the launcher records each resolved gang size). Feeds the /statusz
# supervisor section so a shrunken gang is visible in mission control:
# current attempt's world vs the previous attempt's. The parallel
# stamps list (wall-clock start of each attempt) feeds the chip-hour
# utilization view; kept separate so tests that monkeypatch
# _attempt_worlds alone keep working.
_attempt_worlds = []
_attempt_stamps = []


def record_attempt_world(num_workers):
    """Launcher hook: one resolved gang size per launch attempt."""
    _attempt_worlds.append(int(num_workers))
    _attempt_stamps.append(time.time())


def attempt_world_sizes():
    """World sizes of this driver's launch attempts, oldest first."""
    return list(_attempt_worlds)


def attempt_chip_hours(now=None):
    """Chip-hours per attempt (world size x attempt wall duration):
    the /statusz utilization ledger of what an elastic run actually
    spent. The last attempt is priced up to ``now``. Attempts whose
    start stamp is unknown (tests monkeypatching _attempt_worlds)
    price as None rather than guessing."""
    now = time.time() if now is None else now
    out = []
    for i, world in enumerate(_attempt_worlds):
        t0 = _attempt_stamps[i] if i < len(_attempt_stamps) else None
        if t0 is None:
            out.append({"attempt": i + 1, "world": world,
                        "chip_hours": None})
            continue
        t1 = (_attempt_stamps[i + 1]
              if i + 1 < len(_attempt_stamps) else now)
        out.append({
            "attempt": i + 1, "world": world,
            "chip_hours": round(world * max(0.0, t1 - t0) / 3600.0, 6),
        })
    return out

TRANSIENT = "transient"
PERMANENT = "permanent"

# Infrastructure signatures in worker tracebacks (case-insensitive
# substring match). An EXC frame matching one of these is the gang
# runtime failing, not the user's main: the rank observing a peer's
# preemption raises a connection/collective error of its own, and
# classifying that as "user code" would veto the retry the preempted
# gang exists to get. Extend via SPARKDL_TPU_TRANSIENT_PATTERNS.
TRANSIENT_SIGNATURES = (
    "connection reset",
    "connection closed",
    "connection refused",
    "connection aborted",
    "broken pipe",
    "socket closed",
    "address already in use",       # coordinator/control-plane port clash
    "deadline_exceeded",
    "deadline exceeded",
    "unavailable:",                 # grpc status prefix
    "failed to connect",
    "coordination service",         # jax.distributed heartbeats
    "heartbeat",
    "barrier timed out",
    "rendezvous",
    "gloo",                         # CPU-rig collective runtime
    "preempt",
)


class GangFailure(RuntimeError):
    """A launched gang failed. Carries the structured evidence the
    supervisor classifies on: ``kind`` (``"rendezvous_timeout"``,
    ``"worker_death"``, ``"start_failure"``, ``"no_result"``,
    ``"hang"``), per-rank ``exit_codes`` (negative = killed by that
    signal), ``exceptions`` (rank → traceback text from EXC frames),
    and for hangs the detector's ``hang_verdict``
    (``straggler``/``deadlock``). Subclasses RuntimeError so
    pre-supervisor callers keep working."""

    def __init__(self, message, *, kind="unknown", exit_codes=None,
                 exceptions=None, hang_verdict=None):
        super().__init__(message)
        self.kind = kind
        self.exit_codes = list(exit_codes or [])
        self.exceptions = dict(exceptions or {})
        self.hang_verdict = hang_verdict


@dataclasses.dataclass
class AttemptRecord:
    """One launch attempt, as named in the exhaustion error."""
    number: int
    verdict: str       # TRANSIENT | PERMANENT
    cause: str

    def __str__(self):
        return f"attempt {self.number}: {self.verdict} — {self.cause}"


class GangRetryBudgetExhausted(RuntimeError):
    """Every relaunch in the budget failed transiently. The message
    names every attempt with its classified cause — the loud final
    word the acceptance contract requires."""

    def __init__(self, attempts, budget):
        self.attempts = list(attempts)
        self.budget = budget
        lines = "\n".join(f"  {a}" for a in self.attempts)
        super().__init__(
            f"HorovodRunner gang failed {len(self.attempts)} time(s); "
            f"retry budget ({budget} relaunch(es)) exhausted. "
            f"Attempt log:\n{lines}"
        )


def _extra_patterns():
    raw = os.environ.get(EXTRA_PATTERNS_ENV, "")
    return tuple(p.strip().lower() for p in raw.split(";") if p.strip())


def _terminal_block(tb_text):
    """The traceback's final exception message: from the last
    unindented non-header line (``SomeError: message``) to the end, so
    multi-line messages are kept. Frame lines (``File "/u/gloo.py"``)
    and source echoes are excluded — a user file PATH or source line
    mentioning 'gloo'/'rendezvous' must never read as infrastructure."""
    lines = tb_text.rstrip().splitlines()
    start = 0
    for i, ln in enumerate(lines):
        if (ln and not ln[0].isspace()
                and not ln.startswith("Traceback (")
                and not ln.startswith("During handling")
                and not ln.startswith("The above exception")):
            start = i
    return "\n".join(lines[start:])


def _is_infra_traceback(tb_text):
    """True when a worker's EXC frame is the distributed runtime
    failing (connection/collective/rendezvous errors), not user code.
    Checked against the TERMINAL exception block only — type line plus
    its message — never against file paths or source lines, so user
    code that merely lives near infrastructure vocabulary stays
    classified as user code (and is never retried)."""
    if not tb_text.strip():
        return False
    term = _terminal_block(tb_text)
    if re.match(
        r"(\w+\.)*(Connection(Reset|Refused|Aborted)?Error|"
        r"BrokenPipeError|TimeoutError|socket\.timeout)\b",
        term,
    ):
        return True
    low = term.lower()
    return any(
        sig in low for sig in TRANSIENT_SIGNATURES + _extra_patterns()
    )


def classify_failure(exc):
    """(verdict, cause): *permanent* failures are never retried.

    Failure classes (ISSUE: preemption-aware supervision):

    - Typed launcher errors (slot exhaustion/probe/wait, remote
      transport) and bad arguments → permanent; the launcher already
      documents why each cannot self-heal.
    - A worker EXC frame that is NOT an infrastructure error →
      permanent: user code raised, and rerunning user bugs burns pod
      hours to reproduce them.
    - Rendezvous timeouts, lost results, ranks killed by signals
      (SIGKILL is what preemption looks like from the driver),
      detector-declared gang hangs (``kind="hang"`` — the HANG
      cause), and infrastructure-only EXC frames → transient.
    - Anything else (e.g. a worker exiting 1 with no traceback — a
      bootstrap crash such as an import error) → permanent: retrying
      what we cannot name would hide real breakage.
    """
    # Local import: launcher imports this module at call time too, and
    # a module-level circular import would order-lock the two.
    from sparkdl_tpu.horovod.launcher import (
        RemoteTransportError,
        SlotExhaustionError,
        SlotProbeError,
        SlotWaitTimeout,
    )

    if isinstance(exc, (SlotExhaustionError, SlotProbeError,
                        SlotWaitTimeout, RemoteTransportError)):
        return PERMANENT, f"{type(exc).__name__} (cannot self-heal)"
    if isinstance(exc, (ValueError, TypeError)):
        return PERMANENT, f"bad arguments ({type(exc).__name__})"
    if isinstance(exc, GangFailure):
        if exc.kind == "elastic_resize":
            # Not a failure at all: the elastic controller asked the
            # launcher to recycle the gang at a new np after a
            # checkpoint boundary (capacity returned, or the chip
            # arbiter moved chips between training and serving). The
            # relaunch is the whole point — transient by construction,
            # and the supervise loop charges it zero retry budget and
            # zero backoff. Checked FIRST for the same reason as hang:
            # the launcher's own kill makes the exit codes look
            # signal-killed.
            return TRANSIENT, (
                f"ELASTIC ({getattr(exc, 'elastic_direction', 'resize')}"
                f") — planned resize to "
                f"np={getattr(exc, 'elastic_target', '?')}; relaunching "
                "from checkpoint"
            )
        if exc.kind == "hang":
            # The hang detector declared the gang wedged (one rank
            # stuck in a collective, a stalled host callback...) and
            # the launcher already captured stack dumps and killed the
            # workers. From the outside this is preemption-shaped: the
            # run state is intact in the checkpoint, a relaunch
            # resumes it — classify transient under the HANG cause.
            # Checked FIRST: the launcher's own kill makes the exit
            # codes look signal-killed, and a mid-kill EXC frame must
            # not re-classify a diagnosed hang as user code.
            return TRANSIENT, (
                f"HANG ({exc.hang_verdict or 'hung'}) — gang made no "
                "progress; stack dumps captured, relaunching from "
                "checkpoint"
            )
        user_ranks = [
            r for r, tb in sorted(exc.exceptions.items())
            if not _is_infra_traceback(tb)
        ]
        if user_ranks:
            return PERMANENT, (
                f"user-code exception on rank(s) {user_ranks}"
            )
        if exc.kind == "rendezvous_timeout":
            return TRANSIENT, "gang rendezvous timed out"
        if exc.kind == "no_result":
            return TRANSIENT, "rank 0 result lost on the control plane"
        killed = [
            (r, -c) for r, c in enumerate(exc.exit_codes) if c and c < 0
        ]
        if killed:
            return TRANSIENT, (
                "rank(s) killed by signal "
                + ", ".join(f"{r} (sig {s})" for r, s in killed)
                + " — preemption-like"
            )
        if exc.exceptions:  # all infra tracebacks, no signal deaths
            return TRANSIENT, (
                "infrastructure failure on rank(s) "
                f"{sorted(exc.exceptions)}"
            )
        return PERMANENT, (
            f"unclassified worker failure (kind={exc.kind}, exit codes "
            f"{exc.exit_codes}) — not retried blindly"
        )
    return PERMANENT, f"unclassified {type(exc).__name__} (not retried)"


@dataclasses.dataclass
class RetryPolicy:
    """Relaunch budget + backoff schedule + resume source."""
    max_retries: int = 0
    backoff_base: float = 1.0
    backoff_factor: float = 2.0
    backoff_max: float = 60.0
    jitter: float = 0.5
    resume_dir: str = None

    @classmethod
    def from_env(cls, env=None):
        env = os.environ if env is None else env
        retries = env.get(GANG_MAX_RETRIES_ENV)
        if retries is None:
            # Legacy knob: same budget, but under the new policy only
            # TRANSIENT failures consume it (retrying a user exception
            # was always a bug amplifier).
            retries = env.get(LEGACY_MAX_RESTARTS_ENV, "0")
        return cls(
            max_retries=int(retries),
            backoff_base=float(env.get(BACKOFF_BASE_ENV, "1.0")),
            backoff_factor=float(env.get(BACKOFF_FACTOR_ENV, "2.0")),
            backoff_max=float(env.get(BACKOFF_MAX_ENV, "60.0")),
            jitter=float(env.get(BACKOFF_JITTER_ENV, "0.5")),
            resume_dir=env.get(RESUME_DIR_ENV) or None,
        )

    def backoff(self, attempt, _random=random.random):
        """Delay before relaunch #``attempt`` (1-based): capped
        exponential plus up to ``jitter`` fraction on top."""
        base = min(
            self.backoff_base * self.backoff_factor ** (attempt - 1),
            self.backoff_max,
        )
        return base * (1.0 + self.jitter * _random())


def _resume_step(policy):
    if not policy.resume_dir:
        return None
    from sparkdl_tpu.utils.checkpoint import latest_complete_step

    return latest_complete_step(policy.resume_dir)


def _relaunch_np_target():
    """The elastic-relaunch target np, or None (keep the configured
    np). The operator's env knob always wins; with it unset, the
    active :class:`~sparkdl_tpu.horovod.elastic.ElasticController` (if
    any) answers — a planned resize's target, or the current world
    clamped to the probed capacity. Unparsable operator input is
    logged, never fatal: a typo must not take down an otherwise-
    recoverable gang."""
    raw = os.environ.get(RELAUNCH_NP_ENV)
    if raw:
        try:
            return int(raw)
        except ValueError:
            logger.warning(
                "ignoring unparsable %s=%r (want an integer np)",
                RELAUNCH_NP_ENV, raw,
            )
            return None
    from sparkdl_tpu.horovod import elastic

    ctrl = elastic.active_controller()
    if ctrl is not None:
        try:
            return ctrl.relaunch_target()
        except Exception:
            logger.warning("elastic relaunch-target probe failed",
                           exc_info=True)
    return None


def _reshard_preflight(target_np):
    """Feasibility-gate an elastic relaunch at ``target_np`` BEFORE the
    backoff sleep: an infeasible shrink raises the typed
    ``ReshardPreflightError`` (naming the failing param/axis) here on
    the driver, where it costs a log line — not mid-restore on the
    chips, where it costs the pod an OOM. Returns the ReshardPlan, or
    None when no sharding tree was registered (nothing provable; the
    relaunch proceeds unchecked)."""
    from sparkdl_tpu import observe
    from sparkdl_tpu.analysis.comms import (
        ReshardPreflightError,
        check_relaunch_np,
    )

    try:
        plan = check_relaunch_np(target_np)
    except ReshardPreflightError as e:
        observe.instant(
            "gang.reshard_refused", cat="supervisor",
            target_np=target_np,
            problems=[str(f) for f in e.findings[:4]],
        )
        logger.error(
            "elastic relaunch at np=%d refused by the reshard "
            "pre-flight; not relaunching: %s", target_np, e,
        )
        raise
    if plan is not None:
        observe.instant(
            "gang.reshard_preflight", cat="supervisor",
            target_np=target_np, feasible=True,
            restore_high_water_bytes=plan.restore_high_water_bytes,
        )
        logger.info(
            "elastic relaunch at np=%d cleared the reshard pre-flight "
            "(target mesh %s, restore high-water %.2f GiB)",
            target_np, plan.target_axes,
            plan.restore_high_water_bytes / 2**30,
        )
    return plan


def _reshard_axes(policy, target_np, resume_step):
    """(source_axes, target_axes) for an elastic relaunch's restart
    context: source from the registered gang sharding when the driver
    registered one, else from the resume checkpoint's sharding-tree
    sidecar (jax-free — readable on the driver between relaunches);
    target derived via ``shrink_mesh``. ``(None, None)`` when no
    source mesh is knowable — workers then fall back to their own
    world-size defaults."""
    from sparkdl_tpu.analysis.comms import (
        registered_gang_sharding,
        shrink_mesh,
    )

    src = None
    reg = registered_gang_sharding()
    if reg is not None:
        src = dict(reg["source_axes"])
    if not src and policy.resume_dir and resume_step is not None:
        from sparkdl_tpu.utils.checkpoint import (
            load_sharding_tree,
            sidecar_mesh_axes,
        )

        doc = load_sharding_tree(policy.resume_dir, resume_step)
        if doc is not None:
            src = sidecar_mesh_axes(doc)
    if not src:
        return None, None
    tgt, _reason = shrink_mesh(src, int(target_np))
    return src, tgt


def supervise(launch, policy, _sleep=time.sleep):
    """Run ``launch(extra_env)`` under the retry policy.

    ``launch`` is called with the env delta to merge into every
    worker's environment (the restart context); it must raise on
    failure and return the gang result on success. The first attempt
    ships no context (unmodified mains see attempt 0 / no resume
    step); each relaunch ships the incremented attempt number and the
    newest committed checkpoint step.
    """
    from sparkdl_tpu import observe
    from sparkdl_tpu.utils import locksan

    # Opt-in lock-order sanitizer (SPARKDL_TPU_CONCUR_SAN=1): installed
    # before the supervisor spins up control plane / elastic threads so
    # every lock they construct is instrumented from birth.
    locksan.maybe_install()

    attempts = []
    attempt = 1
    budget_used = 0  # only UNPLANNED transient failures consume budget
    del _attempt_worlds[:]  # fresh story per supervised launch
    del _attempt_stamps[:]
    while True:
        extra_env = {}
        if attempt > 1:
            extra_env[RESTART_ATTEMPT_ENV] = str(attempt - 1)
            step = _resume_step(policy)
            if step is not None:
                extra_env[RESUME_STEP_ENV] = str(step)
            target_np = _relaunch_np_target()
            if target_np is not None:
                # Cleared by _reshard_preflight before the backoff
                # that led here; shipped so the relaunched workers see
                # the elastic target — the launcher resizes the gang
                # to it, and the axes pair below tells worker mains
                # the exact mesh to rebuild (recorded source layout +
                # shrink_mesh-derived target).
                extra_env[RELAUNCH_NP_ENV] = str(target_np)
                src_axes, tgt_axes = _reshard_axes(
                    policy, target_np, step)
                if src_axes:
                    extra_env[RESHARD_SOURCE_AXES_ENV] = json.dumps(
                        src_axes, sort_keys=True)
                if tgt_axes:
                    extra_env[RESHARD_TARGET_AXES_ENV] = json.dumps(
                        tgt_axes, sort_keys=True)
        observe.inc("gang_attempts_total")
        observe.instant("gang.attempt", cat="supervisor", attempt=attempt)
        try:
            return launch(extra_env)
        except Exception as e:
            verdict, cause = classify_failure(e)
            planned = getattr(e, "kind", None) == "elastic_resize"
            attempts.append(AttemptRecord(attempt, verdict, cause))
            first_line = (str(e).splitlines() or ["<no message>"])[0]
            if planned:
                # A controller-requested resize, not a failure: no
                # failure instant/counter, no budget charge, no
                # backoff — the checkpoint-boundary wait already
                # happened before the launcher recycled the gang.
                observe.instant(
                    "gang.resize", cat="supervisor", attempt=attempt,
                    cause=cause,
                    direction=getattr(e, "elastic_direction", None),
                    target_np=getattr(e, "elastic_target", None),
                )
                logger.info(
                    "HorovodRunner gang recycling for a planned "
                    "elastic resize (attempt %d: %s)", attempt, cause,
                )
            else:
                # Every AttemptRecord lands on the gang timeline with
                # its classify_failure verdict — the "classified
                # transient" beat of a chaos run's story — and in the
                # metric view (gang_failures_total by verdict,
                # alertable).
                observe.instant(
                    "gang.failure", cat="supervisor", attempt=attempt,
                    verdict=verdict, cause=cause,
                    kind=getattr(e, "kind", type(e).__name__),
                )
                observe.inc("gang_failures_total", verdict=verdict)
            if verdict == PERMANENT:
                logger.error(
                    "HorovodRunner gang failed permanently on attempt "
                    "%d (%s); not retrying: %s",
                    attempt, cause, first_line,
                )
                raise
            if not planned:
                budget_used += 1
                if budget_used > policy.max_retries:
                    if policy.max_retries > 0:
                        raise GangRetryBudgetExhausted(
                            attempts, policy.max_retries
                        ) from e
                    raise  # supervision off: surface untouched
            target_np = _relaunch_np_target()
            if target_np is not None:
                # Elastic relaunch: feasibility-check the resized
                # mesh BEFORE paying the backoff sleep — an
                # infeasible target raises the typed refusal here.
                # A controller-planned target that fails pre-flight
                # is cancelled instead (the relaunch proceeds at the
                # current np); only the operator's explicit env
                # target escalates the refusal.
                try:
                    _reshard_preflight(target_np)
                except Exception:
                    if os.environ.get(RELAUNCH_NP_ENV):
                        raise
                    from sparkdl_tpu.horovod import elastic

                    ctrl = elastic.active_controller()
                    if ctrl is None:
                        raise
                    ctrl.cancel_pending("reshard_preflight_refused")
            if planned:
                delay = 0.0
            else:
                delay = policy.backoff(budget_used)
            # Recomputed at the top of the next iteration too (listdir
            # is cheap); shown here so the operator sees the resume
            # point BEFORE the backoff sleep, not after.
            resume = _resume_step(policy)
            from sparkdl_tpu.parallel.compile import (
                persistent_cache_dir,
            )

            warm = persistent_cache_dir()
            if not planned:
                logger.warning(
                    "HorovodRunner gang failed transiently (attempt "
                    "%d, retry %d/%d: %s); relaunching in %.1fs%s%s: "
                    "%s",
                    attempt, budget_used, policy.max_retries, cause,
                    delay,
                    "" if resume is None
                    else f" (will resume from step {resume})",
                    "" if not warm else " (compile cache warm)",
                    first_line,
                )
            observe.inc("gang_restarts_total")
            if delay > 0:
                with observe.span("gang.backoff", cat="supervisor",
                                  attempt=attempt,
                                  delay_s=round(delay, 3),
                                  resume_step=resume):
                    _sleep(delay)
            attempt += 1
