"""Autotuned performance profiles: schema, storage, and the launcher
pre-flight that applies them.

A profile is the autotuner's emitted winner — a small JSON document
(schema ``sparkdl_tpu.perf.profile/1``) mapping registered *tunable*
env knobs to values, keyed by the device kind it was measured on and
stamped with the host fingerprint + git sha that measured it:

.. code-block:: json

    {"schema": "sparkdl_tpu.perf.profile/1",
     "device_kind": "cpu",
     "host": "host/x86_64/cpu64",
     "git_sha": "1b268b0", "created": "2026-08-04T00:00:00Z",
     "bench": "gbdt",
     "status": "verified",
     "knobs": {"SPARKDL_TPU_GBDT_MAX_BINS": "64"},
     "evidence": {"...": "trial + verification compare reports"}}

Committed profiles live one-per-(device kind, bench) under
``benchmarks/profiles/<kind>/<bench>.json`` — benches tune disjoint
knob subsets, so a kind composes its per-bench profiles. The launcher
pre-flight (:func:`preflight_env`, called by ``_launch_gang_once`` for
every attempt) resolves every profile for the launch's device kind and
merges their knobs into each worker's environment **under the
operator**: a
knob already present in the driver's env is never overridden — the
profile supplies defaults, the operator keeps the last word. Because
application happens per attempt inside the launch function the
supervisor retries, a relaunched gang re-inherits the profile through
exactly the env-forwarding path the restart context rides (pinned by
``tests/perf/test_profile.py``).

Proof-or-degrade (the PR 9 fix-engine contract): the autotuner only
emits ``status: "verified"`` after a fresh winner-vs-default
verification trial passes the ``observe.compare`` gate. A winner whose
verification regresses is emitted as ``status: "degraded"`` — the
document records the candidate knobs and the failing compare report,
but :func:`preflight_env` applies **nothing** and logs why. Unknown or
non-tunable knob names in a profile are skipped loudly, never
exported: a profile must not become an arbitrary-env injection path.

``SPARKDL_TPU_PERF_PROFILE`` steers resolution: unset = the committed
``benchmarks/profiles/`` directory; a directory = per-device-kind
lookup there; a file = exactly that profile; ``0``/``off`` = disabled.
"""

import json
import logging
import os
import sys
import time

logger = logging.getLogger("sparkdl.perf")

PROFILE_SCHEMA = "sparkdl_tpu.perf.profile/1"
PROFILE_ENV = "SPARKDL_TPU_PERF_PROFILE"

STATUS_VERIFIED = "verified"
STATUS_DEGRADED = "degraded"


class ProfileError(ValueError):
    """A profile document violates the schema contract."""


def default_profile_dir():
    """``benchmarks/profiles`` at the repo root — the committed home
    of per-device-kind profiles."""
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(root, "benchmarks", "profiles")


# Raw device-kind tokens we can honestly key a profile by. Not
# observe.perf.normalize_device_kind: that helper raises on an unknown
# kind, and here unknown is an answer — None, no profile (a
# WORKER_PLATFORM=tpu pin on a v4 pod must not load v5e-measured
# knobs).
_KIND_TOKENS = (("v5p", "v5p"), ("v5e", "v5e"), ("v5 lite", "v5e"),
                ("v5lite", "v5e"), ("v4", "v4"), ("cpu", "cpu"))


def strict_device_kind(raw):
    """Normalize a raw device-kind/platform string, or None when the
    kind cannot be named with confidence (never a default guess)."""
    if not raw:
        return None
    low = str(raw).lower()
    for token, kind in _KIND_TOKENS:
        if token in low:
            return kind
    return None


def profile_path(device_kind, bench, root=None):
    """Committed home of one (device kind, bench) profile:
    ``benchmarks/profiles/<kind>/<bench>.json`` — benches tune
    disjoint knob subsets, so a kind keeps one profile per bench and
    the pre-flight applies their union. The kind must resolve
    strictly; keying a profile by a guessed kind would misfile it."""
    kind = strict_device_kind(device_kind)
    if kind is None:
        raise ProfileError(
            f"cannot key a profile by device kind {device_kind!r} "
            "(unknown kind — profiles are measurements, not guesses)")
    return os.path.join(root or default_profile_dir(), kind,
                        f"{bench}.json")


def make_profile(knobs_map, *, device_kind, bench, status,
                 evidence=None, candidate_knobs=None):
    """Build one schema-versioned profile doc. ``knobs_map`` must name
    registered TUNABLE knobs only (the apply side re-checks, but a
    malformed profile should fail at emit time, where the autotuner
    can see it)."""
    from sparkdl_tpu.observe import perf as operf
    from sparkdl_tpu.utils import knobs as knob_reg

    if status not in (STATUS_VERIFIED, STATUS_DEGRADED):
        raise ProfileError(f"unknown profile status {status!r}")
    for name in knobs_map:
        kb = knob_reg.get(name)
        if kb is None or not kb.tunable:
            raise ProfileError(
                f"profile knob {name!r} is not a registered tunable "
                "knob (sparkdl_tpu/utils/knobs.py)")
    doc = {
        "schema": PROFILE_SCHEMA,
        "device_kind": device_kind,
        "host": operf.host_fingerprint(),
        "git_sha": operf.git_sha(),
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "bench": bench,
        "status": status,
        "knobs": {k: str(v) for k, v in knobs_map.items()},
    }
    if candidate_knobs:
        # the degraded case: what the search picked before the
        # verification trial refused it — kept for the postmortem
        doc["candidate_knobs"] = {
            k: str(v) for k, v in candidate_knobs.items()}
    if evidence:
        doc["evidence"] = evidence
    return doc


def save_profile(doc, path=None):
    path = path or profile_path(doc.get("device_kind"),
                                doc.get("bench"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_profile(path):
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise ProfileError(f"unreadable profile {path}: {e}")
    if not isinstance(doc, dict) or doc.get("schema") != PROFILE_SCHEMA:
        raise ProfileError(
            f"{path} is not a {PROFILE_SCHEMA} document "
            f"(schema={doc.get('schema') if isinstance(doc, dict) else None!r})")
    if not isinstance(doc.get("knobs"), dict):
        raise ProfileError(f"{path} has no knobs map")
    return doc


def _initialized_backend_kind():
    """The probed device kind, but ONLY when this process's jax
    backend is already live (``operf.device_kind()`` never initializes
    one): the launcher pre-flight runs in the DRIVER, where a
    first-touch TPU init would take the chip out from under the
    workers it is about to spawn."""
    from sparkdl_tpu.observe import perf as operf

    return strict_device_kind(operf.device_kind())


def resolve_launch_device_kind(env=None):
    """The device kind a launch is about to run on, WITHOUT
    initializing a backend in the driver (the telemetry no-import
    rule, tightened to no-*init*): an operator platform pin wins, then
    an already-INITIALIZED jax backend's probed kind, then the absence
    of accelerator device nodes (no ``/dev/accel*`` = cpu). Anything
    that cannot be named with confidence (a bare ``tpu`` pin, device
    nodes with no live backend) returns None — applying another
    kind's profile would be a guess, and profiles are measurements."""
    env = os.environ if env is None else env
    pinned = env.get("SPARKDL_TPU_WORKER_PLATFORM") \
        or env.get("SPARKDL_TPU_BENCH_PLATFORM")
    if pinned:
        return strict_device_kind(pinned)
    kind = _initialized_backend_kind()
    if kind is not None:
        return kind
    import glob

    if not (glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")
            or glob.glob("/dev/nvidia*")):
        return "cpu"
    return None


def find_profiles(env=None):
    """Resolve every profile applicable to this launch, in
    deterministic (bench-name) order. Returns ``[(doc, path), ...]``
    (empty when none apply — the common case for a host class with no
    committed profiles). An EXPLICIT ``SPARKDL_TPU_PERF_PROFILE``
    path that names neither a file nor a directory raises — an
    operator who pinned a profile must never silently run without it
    — and a malformed profile raises (committed artifacts must not
    rot silently)."""
    import glob as globmod

    env = os.environ if env is None else env
    spec = (env.get(PROFILE_ENV) or "").strip()
    if spec.lower() in ("0", "off", "none"):
        return []
    if spec and os.path.isfile(spec):
        return [(load_profile(spec), spec)]
    if spec and not os.path.isdir(spec):
        raise ProfileError(
            f"{PROFILE_ENV}={spec} is neither a profile file nor a "
            "profile directory")
    root = spec if spec else default_profile_dir()
    kind = resolve_launch_device_kind(env)
    if kind is None:
        return []
    paths = sorted(globmod.glob(
        os.path.join(root, kind, "*.json")))
    # legacy flat spelling (<root>/<kind>.json) still honored
    flat = os.path.join(root, f"{kind}.json")
    if os.path.isfile(flat):
        paths.append(flat)
    out = []
    for p in paths:
        try:
            out.append((load_profile(p), p))
        except ProfileError as e:
            # quarantine a rotten profile to itself: one malformed
            # committed file must not stop the kind's OTHER profiles
            # from applying
            logger.warning("perf profile %s ignored: %s", p, e)
    return out


def profile_env_delta(doc, base_env):
    """The env vars a profile contributes UNDER ``base_env``: only
    registered tunable knobs, only where the operator has not already
    set the var, and nothing at all from a degraded profile."""
    from sparkdl_tpu.utils import knobs as knob_reg

    if doc.get("status") != STATUS_VERIFIED:
        logger.warning(
            "perf profile (bench=%s, device_kind=%s) is %s — "
            "verification regressed vs defaults; running on defaults",
            doc.get("bench"), doc.get("device_kind"),
            doc.get("status"))
        return {}
    delta = {}
    for name, value in sorted(doc.get("knobs", {}).items()):
        kb = knob_reg.get(name)
        if kb is None or not kb.tunable:
            logger.warning(
                "perf profile names %r, which is not a registered "
                "tunable knob — skipped (profiles are not an env "
                "injection path)", name)
            continue
        if name in base_env:
            # operator keeps the last word
            continue
        delta[name] = str(value)
    return delta


def preflight_env(base_env=None):
    """The launcher pre-flight: resolve + apply every profile for this
    launch (benches tune disjoint knob subsets, so a device kind's
    per-bench profiles compose; a knob two profiles both name keeps
    the first and logs the conflict). Returns the env delta to merge
    into every worker env (empty when nothing applies). Logs one line
    per applying profile; a cross-host profile (same device kind,
    different fingerprint) applies but says so — same advisory honesty
    as ``observe.compare``. Never raises: a broken profile must not
    take down a launch (it logs and degrades to defaults)."""
    from sparkdl_tpu.observe import perf as operf

    base_env = os.environ if base_env is None else base_env
    delta = {}
    try:
        for doc, path in find_profiles(base_env):
            one = profile_env_delta(doc, base_env)
            for name in sorted(set(one) & set(delta)):
                logger.warning(
                    "perf profile %s also names %s (=%s); keeping the "
                    "earlier profile's %s", path, name, one[name],
                    delta[name])
                one.pop(name)
            if one:
                cross = (doc.get("host")
                         and doc.get("host") != operf.host_fingerprint())
                logger.info(
                    "perf profile %s (bench=%s, device_kind=%s%s): "
                    "applying %s",
                    path, doc.get("bench"), doc.get("device_kind"),
                    " — measured on a DIFFERENT host, advisory numbers"
                    if cross else "",
                    ", ".join(f"{k}={v}"
                              for k, v in sorted(one.items())))
            delta.update(one)
        return delta
    except ProfileError as e:
        logger.warning("perf profile ignored: %s", e)
        return delta
    except Exception:
        logger.warning("perf profile pre-flight failed; launching on "
                       "defaults", exc_info=True)
        return delta


def main(argv=None):
    """``python -m sparkdl_tpu.perf.profile [PATH]``: show the profile
    that would apply to a launch from this environment (or validate an
    explicit PATH) — the operator's dry-run of the pre-flight."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m sparkdl_tpu.perf.profile",
        description="Inspect/validate autotuned perf profiles.")
    ap.add_argument("path", nargs="?", help="profile JSON to validate "
                    "(default: resolve like the launcher pre-flight)")
    args = ap.parse_args(argv)
    if args.path:
        doc = load_profile(args.path)
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 0
    found = find_profiles()
    if not found:
        print("no profile applies to this environment "
              f"(device kind: {resolve_launch_device_kind()!r})")
        return 1
    delta = preflight_env(os.environ)
    for doc, path in found:
        print(f"profile: {path} (bench={doc.get('bench')}, "
              f"status={doc.get('status')})")
    print(json.dumps({"would_apply": delta}, indent=2, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
