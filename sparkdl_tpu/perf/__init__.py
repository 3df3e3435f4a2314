"""``sparkdl_tpu.perf``: the self-tuning runtime — close the loop from
ledger to knobs (ISSUE 12 tentpole; ROADMAP D5 holds it as a debt: it
has searched CPU timings only and adopted nothing).

The platform *measures* everything (PR 7 attribution/MFU, the
``history.jsonl`` ledger, ``observe.compare``'s noise-aware medians)
and *rewrites* programs under machine-checked proofs (PR 9 lint-to-fix)
— this package composes the two into an autotuner:

- :mod:`sparkdl_tpu.perf.autotune` — the search driver. Derives its
  knob space from the :mod:`sparkdl_tpu.utils.knobs` registry (knobs
  are data, not code — the XGBoost-``hist`` idiom: the method is
  fixed, the bins are searched), runs short measured trials through
  the EXISTING bench harnesses (``benchmarks/serve_bench.py``,
  ``gbdt_bench.py``, ``attention_bench.py``), judges
  every candidate with ``observe.compare``'s rep-sample medians + IQR
  thresholds (never a single timed invocation), and prunes the space
  with step-time attribution — a step that is 80% compute never
  explores prefetch depth; a serving run with near-zero queue wait
  never explores ``max_queue``.
- :mod:`sparkdl_tpu.perf.profile` — the committed per-device-kind
  profile the winner is emitted as (schema
  ``sparkdl_tpu.perf.profile/1``, keyed by device kind + host
  fingerprint), and the launcher pre-flight that applies it through
  the same worker-env forwarding path every supervised relaunch
  already inherits. The PR 9 proof-or-degrade contract carries over:
  a profile is only emitted ``verified`` after a fresh
  winner-vs-default verification trial passes the compare gate;
  a regressing winner degrades to defaults — and says so.

CLI: ``python -m sparkdl_tpu.perf.autotune --bench gbdt|serve|attention``.
"""

from sparkdl_tpu.perf.profile import (  # noqa: F401
    PROFILE_ENV,
    PROFILE_SCHEMA,
    ProfileError,
    load_profile,
    preflight_env,
    save_profile,
)
