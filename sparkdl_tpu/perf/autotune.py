"""The autotune search driver: measured trials over the registered
knob space, judged by ``observe.compare``, emitted as a verified
profile.

Search model (deliberately boring — the budget is wall-clock, not
cleverness): greedy coordinate descent over the tunable knobs a trial
harness honors. One baseline trial on defaults, then per knob each
declared candidate value measured against the current best config;
the best *improving* value (per the compare gate's median/IQR verdict
— a noisy-but-flat knob is a tie, never an improvement) is adopted
before the next knob. Every measured trial is one run of a REAL bench
harness appending its own ``history.jsonl`` ledger line, so the
search leaves the same audit trail a human benchmarking session
would.

Pruning: before any trial, the declared space is filtered against a
step-time attribution report (``observe.perf`` breakdown fractions,
or a serving stat report). A knob declares the component that must be
material for it to matter (``knobs.Knob.component``); when the report
shows that component negligible the knob is dropped from the plan and
the drop is LOGGED — a step that is 80% compute never explores
prefetch depth, a serving run with near-zero queue wait never
explores ``max_queue``. No attribution report = no pruning (unknown
is not irrelevant).

Trial accounting is loud: the driver logs the plan (trial count ≤
space size by construction — greedy measures each candidate value
once), refuses a ``--max-trials`` bound it cannot fit instead of
silently truncating, and the emitted profile carries every trial's
compare verdict as evidence.

Proof-or-degrade: a non-empty winner is re-measured — fresh default
run, fresh winner run — and only a verification pass emits
``status: "verified"``. A winner whose verification regresses is
emitted ``status: "degraded"`` (knobs empty, candidate recorded), and
the launcher pre-flight applies nothing.

CLI::

    python -m sparkdl_tpu.perf.autotune --bench gbdt \\
        --values SPARKDL_TPU_GBDT_MAX_BINS=64,256 --reps 3
    python -m sparkdl_tpu.perf.autotune --bench attention --dry-run
"""

import argparse
import dataclasses
import json
import logging
import os
import subprocess
import sys

from sparkdl_tpu.observe.compare import compare_records
from sparkdl_tpu.perf import profile as profile_mod
from sparkdl_tpu.utils import knobs as knob_reg

logger = logging.getLogger("sparkdl.perf")

ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# A candidate must clear the SAME noise-aware bar the CI gate uses.
DEFAULT_FLOOR = 0.05
DEFAULT_IQR_K = 1.0
# attribution fraction below which a component-gated knob is pruned
MIN_COMPONENT_FRACTION = 0.05
# "a step that is 80% compute never explores prefetch depth"
COMPUTE_BOUND_FRACTION = 0.8


class TrialError(RuntimeError):
    """One measured trial failed (bench crashed, no ledger line)."""


@dataclasses.dataclass
class Trial:
    """One measured configuration and its verdict vs the then-best."""
    overrides: dict
    metrics: dict = None
    decision: str = "failed"     # improved | ok | regression | failed
    delta: float = None          # primary-metric relative delta
    threshold: float = None
    error: str = None


@dataclasses.dataclass
class SearchResult:
    bench: str
    primary_metric: str
    baseline: dict               # ledger-shaped metrics of defaults
    trials: list
    best_overrides: dict
    best_metrics: dict
    pruned: list                 # [(knob name, reason)]
    space_size: int
    device_kind: str = None


# -- trial runners -----------------------------------------------------------


class SubprocessTrialRunner:
    """Run one bench harness as a subprocess with knob overrides in
    its environment, and read the trial's metrics back from the
    ledger line the bench itself appended — the autotuner consumes
    the exact record the CI gate would, not a private side channel.

    ``history_path`` defaults to the repo ledger
    (``benchmarks/results/history.jsonl``): autotune trials are real
    measurements and land in the same memory.
    """

    bench = None                 # registry bench key
    ledger_bench = None          # the `bench` tag its harness writes
    primary_metric = None

    def __init__(self, *, history_path=None, extra_args=(),
                 extra_env=None, timeout=1800):
        from sparkdl_tpu.observe import perf as operf

        self.history_path = history_path or operf.default_history_path()
        self.extra_args = list(extra_args)
        self.extra_env = dict(extra_env or {})
        self.timeout = timeout

    def command(self):
        raise NotImplementedError

    def attribution(self):
        """Breakdown-fractions report used for pruning, or None."""
        return None

    def pick_primary(self, metrics):
        """Primary metric for a runner that declares none: sole metric
        of the ledger line, or a subclass's shape-aware choice."""
        if len(metrics) != 1:
            raise TrialError(
                f"{self.bench} ledger line has {len(metrics)} "
                "metrics and the runner declares no primary")
        return next(iter(metrics))

    def _bounded_run(self, args, env):
        """subprocess with a REAL timeout: a
        child wedged in an accelerator runtime can survive the
        kill-then-communicate path of ``subprocess.run``, so kill the
        whole process group and abandon the pipes after a grace
        period. A timeout is a failed TRIAL (TrialError), never a
        crashed search."""
        import signal

        p = subprocess.Popen(
            args, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, start_new_session=True,
        )
        try:
            out, err = p.communicate(timeout=self.timeout)
            return p.returncode, out, err
        except subprocess.TimeoutExpired:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                p.kill()
            try:
                p.communicate(timeout=10)
            except subprocess.TimeoutExpired:
                pass
            raise TrialError(
                f"{self.bench} trial timed out after {self.timeout}s "
                "(killed)")

    def run(self, overrides):
        from sparkdl_tpu.observe import perf as operf

        env = dict(os.environ)
        env.update(self.extra_env)
        env.update({k: str(v) for k, v in overrides.items()})
        env["SPARKDL_TPU_PERF_HISTORY"] = self.history_path
        before = len(operf.read_history(self.history_path))
        rc, _, err = self._bounded_run(
            self.command() + self.extra_args, env)
        if rc != 0:
            raise TrialError(
                f"{self.bench} trial exited {rc}: "
                f"{err.strip()[-400:]}")
        # Attribute ONLY a line this harness appended during this
        # trial (bench tag checked): the default ledger is shared, and
        # silently adopting a concurrent writer's record would back a
        # "verified" profile with someone else's numbers.
        new = [e for e in operf.read_history(self.history_path)[before:]
               if self.ledger_bench is None
               or e.get("bench") == self.ledger_bench]
        if not new:
            raise TrialError(
                f"{self.bench} trial appended no "
                f"bench={self.ledger_bench!r} ledger line to "
                f"{self.history_path} (ledger disabled, or a "
                "concurrent writer raced the trial?)")
        entry = new[-1]
        self.device_kind = entry.get("device_kind")
        metrics = entry.get("metrics") or {}
        if self.primary_metric is None:
            self.primary_metric = self.pick_primary(metrics)
        if self.primary_metric not in metrics:
            raise TrialError(
                f"{self.bench} ledger line is missing the primary "
                f"metric {self.primary_metric!r}")
        return metrics


class GbdtRunner(SubprocessTrialRunner):
    bench = "gbdt"
    ledger_bench = "gbdt_bench"
    primary_metric = "gbdt_fit_rows_per_sec"

    def command(self):
        return [sys.executable,
                os.path.join(ROOT, "benchmarks", "gbdt_bench.py")]


class ServeRunner(SubprocessTrialRunner):
    bench = "serve"
    ledger_bench = "serve_bench"
    primary_metric = "serve_tokens_per_sec"

    def command(self):
        return [sys.executable,
                os.path.join(ROOT, "benchmarks", "serve_bench.py")]


class AttentionRunner(SubprocessTrialRunner):
    """Flash-attention kernel-leg bench — the tile-knob search target
    (``SPARKDL_TPU_FLASH_BLOCK_Q``/``_KV``). Trials read the A/B
    section's KERNEL ledger line: on TPU that is the real pallas
    kernel, on cpu the interpret-mode emulation — tile choices change
    the measured program either way, which is what makes the search
    meaningful off-hardware (the fallback leg would be tile-blind on
    cpu). The harness emits one ``attn_ms_s{seq}`` metric per
    measured sequence; the shortest is the primary (the serving-side
    regime), and verification still holds the whole record to
    no-worse."""

    bench = "attention"
    ledger_bench = "attention_bench:kernel"

    def command(self):
        return [sys.executable,
                os.path.join(ROOT, "benchmarks", "attention_bench.py")]

    def attribution(self):
        # static, by construction and not by measurement: one jitted
        # kernel scan — no input pipeline, no collectives
        return {
            "source": "static:attention_bench jitted kernel scan",
            "fractions": {"compute": 1.0, "data_wait": 0.0,
                          "collective": 0.0, "host_callback": 0.0},
        }

    def pick_primary(self, metrics):
        seqs = sorted(
            (m for m in metrics if m.startswith("attn_ms_s")),
            key=lambda m: int(m.rsplit("_s", 1)[1]))
        if not seqs:
            raise TrialError(
                "attention kernel ledger line has no attn_ms_s* metric")
        return seqs[0]


RUNNERS = {"gbdt": GbdtRunner, "serve": ServeRunner,
           "attention": AttentionRunner}


# -- space derivation + pruning ---------------------------------------------


def derive_space(bench, *, knob_names=None, value_overrides=None):
    """The declared search space: ``[(Knob, [values]), ...]`` from the
    registry's tunable knobs for ``bench``. ``knob_names`` restricts
    (and may name any tunable knob — the operator widening the space
    past the declared bench mapping is a decision, not an error);
    ``value_overrides`` (name → list) replaces a knob's declared
    trial values."""
    value_overrides = dict(value_overrides or {})
    if knob_names:
        ks = []
        for name in knob_names:
            kb = knob_reg.get(name)
            if kb is None or not kb.tunable:
                raise SystemExit(
                    f"autotune: {name} is not a registered tunable "
                    "knob (see sparkdl_tpu/utils/knobs.py)")
            ks.append(kb)
    else:
        ks = knob_reg.tunable_knobs(bench)
    space = []
    consumed = set()
    for kb in ks:
        if kb.name in value_overrides:
            consumed.add(kb.name)
        values = [str(v) for v in
                  value_overrides.get(kb.name, kb.trial_values)]
        if values:
            space.append((kb, values))
    unused = sorted(set(value_overrides) - consumed)
    if unused:
        # the loud-accounting contract: a typo'd --values must not
        # silently measure the declared space instead
        raise SystemExit(
            f"autotune: --values for {unused} match no knob in the "
            f"search space ({sorted(kb.name for kb in ks)}); check "
            "the spelling or add --knob")
    return space


def prune_space(space, report, *, min_fraction=MIN_COMPONENT_FRACTION,
                compute_bound=COMPUTE_BOUND_FRACTION):
    """Drop knobs whose gating component a measured (or declared)
    report shows is immaterial. Returns ``(kept, pruned)`` where
    ``pruned`` is ``[(knob name, reason), ...]`` — every drop is
    visible, nothing is silently capped."""
    fractions = (report or {}).get("fractions") or {}
    kept, pruned = [], []
    for kb, values in space:
        if kb.component:
            f = fractions.get(kb.component)
            if (f is None and kb.component == "data_wait"
                    and fractions.get("compute", 0.0) >= compute_bound):
                # the headline pruning rule: a compute-bound step has
                # no data-wait to hide even when the report carries no
                # explicit data_wait row
                f = 0.0
            if f is not None and f < min_fraction:
                pruned.append((kb.name,
                               f"{kb.component} fraction {f:.3f} < "
                               f"{min_fraction:g} "
                               f"(source: {report.get('source')})"))
                continue
        kept.append((kb, values))
    return kept, pruned


def _non_default(kb, values):
    return [v for v in values if v != (kb.default or "")]


# -- judging -----------------------------------------------------------------


def judge(base_metrics, cand_metrics, primary, *, floor=DEFAULT_FLOOR,
          iqr_k=DEFAULT_IQR_K):
    """One compare-gate verdict between two ledger-shaped metric maps:
    ``(decision, delta, threshold)`` on the PRIMARY metric, through
    the exact :func:`observe.compare.compare_records` math the CI
    gate runs — medians of rep samples, IQR-aware thresholds."""
    report = compare_records({"metrics": base_metrics},
                             {"metrics": cand_metrics},
                             floor=floor, iqr_k=iqr_k)
    row = next((r for r in report["metrics"] if r["metric"] == primary),
               None)
    if row is None:
        return "failed", None, None
    return row["status"], row["delta"], row["threshold"]


# -- the search --------------------------------------------------------------


def autotune(runner, space, *, floor=DEFAULT_FLOOR, iqr_k=DEFAULT_IQR_K,
             attribution=None, max_trials=None, log=logger.info):
    """Greedy coordinate-descent search; returns a
    :class:`SearchResult`. ``attribution`` overrides the runner's own
    report (an operator feeding a real telemetry ``perf.json``)."""
    report = attribution if attribution is not None \
        else runner.attribution()
    space, pruned = prune_space(space, report)
    for name, reason in pruned:
        log(f"pruned {name}: {reason}")
    plan = [(kb, v) for kb, values in space
            for v in _non_default(kb, values)]
    space_size = 1
    for kb, values in space:
        space_size *= len(set(values) | {kb.default or ""})
    n_trials = 1 + len(plan)     # baseline + one per candidate value
    log(f"trial plan: {n_trials} measured trial(s) "
        f"(1 baseline + {len(plan)} candidate(s)) over "
        f"{len(space)} knob(s); configuration space size {space_size}; "
        f"pruned {len(pruned)} knob(s)")
    if max_trials is not None and n_trials > max_trials:
        raise SystemExit(
            f"autotune: trial plan needs {n_trials} trials but "
            f"--max-trials={max_trials}; narrow the space with "
            "--knob/--values instead of silently truncating")

    log("measuring baseline (defaults)")
    baseline = runner.run({})
    primary = runner.primary_metric
    best_metrics, best_overrides = baseline, {}
    trials = []
    for kb, values in space:
        adopted = None
        for v in _non_default(kb, values):
            overrides = dict(best_overrides)
            overrides[kb.name] = v
            try:
                metrics = runner.run(overrides)
            except TrialError as e:
                log(f"trial {kb.name}={v} FAILED: {e}")
                trials.append(Trial(overrides=overrides, error=str(e)))
                continue
            decision, delta, thr = judge(
                best_metrics, metrics, primary,
                floor=floor, iqr_k=iqr_k)
            trials.append(Trial(overrides=overrides, metrics=metrics,
                                decision=decision, delta=delta,
                                threshold=thr))
            log(f"trial {kb.name}={v}: {decision}"
                + (f" ({delta:+.1%} vs thr {thr:.1%})"
                   if delta is not None else ""))
            if decision == "improved" and (
                    adopted is None or delta > adopted[2]):
                adopted = (v, metrics, delta)
        if adopted is not None:
            v, metrics, delta = adopted
            best_overrides[kb.name] = v
            best_metrics = metrics
            log(f"adopted {kb.name}={v} ({delta:+.1%})")
    return SearchResult(
        bench=runner.bench, primary_metric=primary, baseline=baseline,
        trials=trials, best_overrides=best_overrides,
        best_metrics=best_metrics, pruned=pruned,
        space_size=space_size,
        device_kind=getattr(runner, "device_kind", None),
    )


def verify_and_emit(runner, result, *, floor=DEFAULT_FLOOR,
                    iqr_k=DEFAULT_IQR_K, log=logger.info):
    """The proof-or-degrade step: re-measure defaults and the winner
    fresh, pass them through the compare gate, and emit the profile
    doc — ``verified`` with the knobs on a pass (ties included: the
    contract is *no worse*, and a tie still pins the searched space),
    ``degraded`` with empty knobs on a regression."""
    evidence = {
        "primary_metric": result.primary_metric,
        "baseline": result.baseline,
        "pruned": [list(p) for p in result.pruned],
        "space_size": result.space_size,
        "trials": [
            {"overrides": t.overrides, "decision": t.decision,
             "delta": t.delta, "threshold": t.threshold,
             **({"error": t.error} if t.error else {})}
            for t in result.trials
        ],
    }
    if not result.best_overrides:
        log("search found no improving knob: defaults are the profile")
        evidence["verification"] = "skipped (empty winner = defaults)"
        return profile_mod.make_profile(
            {}, device_kind=result.device_kind, bench=result.bench,
            status=profile_mod.STATUS_VERIFIED, evidence=evidence)

    log("verification trial: fresh default run")
    v_default = runner.run({})
    log("verification trial: fresh winner run "
        f"({result.best_overrides})")
    v_winner = runner.run(result.best_overrides)
    report = compare_records({"metrics": v_default},
                             {"metrics": v_winner},
                             floor=floor, iqr_k=iqr_k)
    row = next((r for r in report["metrics"]
                if r["metric"] == result.primary_metric), None)
    evidence["verification"] = {
        "default": v_default, "winner": v_winner,
        "primary": row, "regressions": report["regressions"],
    }
    # "no worse" means the WHOLE record: a winner that improves the
    # primary but regresses a co-measured metric (gbdt predict
    # throughput, serve queue wait...) must not verify. Secondary
    # metrics count only when the compare gate's sample protection is
    # live on them (>= 4 rep samples on either side) — degrading a
    # real winner over one unprotected timed invocation would violate
    # the module's own never-a-single-invocation rule.
    def _protected(name):
        for side in (v_default, v_winner):
            samples = (side.get(name) or {}).get("samples") or ()
            if len(samples) >= 4:
                return True
        return False

    secondary_regressions = [
        r["metric"] for r in report["metrics"]
        if r["status"] == "regression"
        and r["metric"] != result.primary_metric
        and _protected(r["metric"])
    ]
    regressed = (row is None or row["status"] == "regression"
                 or bool(secondary_regressions))
    if regressed:
        log("VERIFICATION REGRESSED: degrading to defaults "
            f"(candidate was {result.best_overrides})")
        return profile_mod.make_profile(
            {}, device_kind=result.device_kind, bench=result.bench,
            status=profile_mod.STATUS_DEGRADED,
            candidate_knobs=result.best_overrides, evidence=evidence)
    log(f"verification passed ({row['delta']:+.1%} on "
        f"{result.primary_metric}); emitting verified profile")
    return profile_mod.make_profile(
        result.best_overrides, device_kind=result.device_kind,
        bench=result.bench, status=profile_mod.STATUS_VERIFIED,
        evidence=evidence)


# -- CLI ---------------------------------------------------------------------


def _parse_values(specs):
    out = {}
    for spec in specs or ():
        name, _, vals = spec.partition("=")
        if not vals:
            raise SystemExit(
                f"autotune: --values wants NAME=v1,v2 (got {spec!r})")
        out[name] = [v for v in vals.split(",")]
    return out


def _load_attribution(path):
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise SystemExit(f"autotune: unreadable attribution {path}: {e}")
    # accept a perf.json attribution doc or any breakdown doc — both
    # carry the fractions map the pruner reads
    if not isinstance(doc.get("fractions"), dict):
        raise SystemExit(
            f"autotune: {path} has no 'fractions' map (want an "
            "observe.perf breakdown/attribution document)")
    doc.setdefault("source", path)
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m sparkdl_tpu.perf.autotune",
        description="Search the registered knob space with measured "
                    "bench trials; emit a verified per-device-kind "
                    "profile the launcher pre-flight applies.")
    ap.add_argument("--bench", choices=sorted(RUNNERS), required=True)
    ap.add_argument("--knob", action="append", default=None,
                    help="restrict the space to this knob (repeatable)")
    ap.add_argument("--values", action="append", default=None,
                    metavar="NAME=v1,v2",
                    help="override a knob's trial values (repeatable)")
    ap.add_argument("--floor", type=float, default=DEFAULT_FLOOR)
    ap.add_argument("--iqr-k", type=float, default=DEFAULT_IQR_K)
    ap.add_argument("--attribution", default=None,
                    help="observe.perf breakdown JSON used for "
                    "pruning (default: the runner's own report)")
    ap.add_argument("--history", default=None,
                    help="ledger path for trial lines (default: the "
                    "repo history.jsonl)")
    ap.add_argument("--out", default=None,
                    help="profile output path ('-' = stdout only; "
                    "default: benchmarks/profiles/<kind>/<bench>.json)")
    ap.add_argument("--reps", type=int, default=None,
                    help="per-trial rep count forwarded to harnesses "
                    "that take --reps (gbdt)")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke shapes (SPARKDL_TPU_BENCH_TINY=1)")
    ap.add_argument("--trial-timeout", type=float, default=1800)
    ap.add_argument("--max-trials", type=int, default=None,
                    help="refuse (loudly) a plan larger than this — "
                    "never a silent cap")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the (pruned) trial plan and exit")
    ap.add_argument("--bench-arg", action="append", default=None,
                    help="extra argv token forwarded to the bench "
                    "harness (repeatable)")
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, format="%(message)s")

    extra_args = list(args.bench_arg or ())
    extra_env = {}
    if args.tiny:
        extra_env["SPARKDL_TPU_BENCH_TINY"] = "1"
    if args.reps is not None and args.bench == "gbdt":
        extra_args += ["--reps", str(args.reps)]
    runner = RUNNERS[args.bench](
        history_path=args.history, extra_args=extra_args,
        extra_env=extra_env, timeout=args.trial_timeout)

    space = derive_space(args.bench, knob_names=args.knob,
                         value_overrides=_parse_values(args.values))
    if not space:
        raise SystemExit(
            f"autotune: no tunable knobs registered for bench "
            f"{args.bench!r}")
    attribution = (_load_attribution(args.attribution)
                   if args.attribution else None)

    if args.dry_run:
        report = attribution if attribution is not None \
            else runner.attribution()
        kept, pruned = prune_space(space, report)
        plan = {
            "bench": args.bench,
            "knobs": {kb.name: _non_default(kb, values)
                      for kb, values in kept},
            "pruned": [list(p) for p in pruned],
            "trials": 1 + sum(len(_non_default(kb, v))
                              for kb, v in kept),
        }
        print(json.dumps(plan, indent=2, sort_keys=True))
        return 0

    result = autotune(runner, space, floor=args.floor,
                      iqr_k=args.iqr_k, attribution=attribution,
                      max_trials=args.max_trials)
    doc = verify_and_emit(runner, result, floor=args.floor,
                          iqr_k=args.iqr_k)
    if args.out == "-":
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0
    try:
        path = profile_mod.save_profile(doc, args.out)
    except profile_mod.ProfileError as e:
        # an unkeyable device kind must not discard a finished search
        # (hours of measured trials): print the document, name the
        # problem, let the operator --out it somewhere explicit
        print(json.dumps(doc, indent=2, sort_keys=True))
        print(f"autotune: could not save the profile ({e}); the "
              "document is printed above — rerun with an explicit "
              "--out to keep it", file=sys.stderr)
        return 1
    print(json.dumps({"profile": path, "status": doc["status"],
                      "knobs": doc["knobs"],
                      **({"candidate_knobs": doc["candidate_knobs"]}
                         if "candidate_knobs" in doc else {})},
                     indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
