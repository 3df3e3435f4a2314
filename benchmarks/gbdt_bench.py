#!/usr/bin/env python
"""GBDT training throughput (the sparkdl.xgboost hist path,
BASELINE.json config 4) — the tabular trial harness of the perf
platform.

Like ``serve_bench.py`` and ``attention_bench.py`` (the other two
autotune harnesses), this bench:

- runs a **warm fit** first (XLA compile + trace outside the measured
  window), then ``--reps`` timed fits of the same estimator config,
  reporting the p50/p99 of ``rows*trees/fit_seconds`` with the raw
  per-rep samples — so ``observe.compare``'s median/IQR noise
  protection applies instead of a single timed invocation;
- appends ONE :func:`sparkdl_tpu.observe.perf.history_record` line
  (``bench="gbdt_bench"``) to ``history.jsonl`` unless ``--no-ledger``
  — the ledger gate ROADMAP item 3 asks every workload to pay;
- has a smoke shape (``--tiny`` / ``SPARKDL_TPU_BENCH_TINY=1``) that
  exercises the full measurement path in seconds on CPU;
- honors the registered knob surface: ``SPARKDL_TPU_GBDT_MAX_BINS``
  is the env default for ``max_bin`` (the XGBoost-``hist``
  bins-are-data axis the autotuner searches); an explicit
  ``--max-bins`` wins.

Prints exactly ONE JSON line on stdout (``metric`` /
``value`` / ``rate_samples`` — the shape ``observe.compare`` loads as
a bench record) and exits nonzero on failure.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import time

import numpy as np

METRIC = "gbdt_fit_rows_per_sec"
UNIT = "rows*trees/sec"


def _env_int(name, default):
    from sparkdl_tpu.utils import knobs

    return knobs.read_int(name, default)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--features", type=int, default=None)
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--depth", type=int, default=None)
    ap.add_argument("--max-bins", type=int, default=None,
                    help="histogram bins; default: the "
                         "SPARKDL_TPU_GBDT_MAX_BINS knob, else 256")
    ap.add_argument("--reps", type=int, default=4,
                    help="timed fits after the warm one (p50/p99 + "
                         "rep samples ride the ledger line; >= 4 "
                         "keeps observe.compare's IQR noise guard "
                         "live — _rel_iqr needs 4 samples)")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke shape (seconds on cpu); also via "
                         "SPARKDL_TPU_BENCH_TINY=1")
    ap.add_argument("--no-ledger", action="store_true",
                    help="do not append to the history.jsonl ledger")
    args = ap.parse_args(argv)

    # CI smoke runs name their platform, before this process first
    # touches a device.
    plat = os.environ.get("SPARKDL_TPU_BENCH_PLATFORM")
    if plat:
        import jax

        jax.config.update("jax_platforms", plat)
    import pandas as pd

    from sparkdl.xgboost import XgboostClassifier
    from sparkdl_tpu.observe import perf

    tiny = args.tiny or bool(os.environ.get("SPARKDL_TPU_BENCH_TINY"))
    if tiny:
        n = args.rows or 2_000
        f = args.features or 8
        trees = args.trees or 3
        depth = args.depth or 3
    else:
        n = args.rows or 100_000
        f = args.features or 32
        trees = args.trees or 20
        depth = args.depth or 5
    max_bins = args.max_bins if args.max_bins is not None else _env_int(
        "SPARKDL_TPU_GBDT_MAX_BINS", 256)

    rng = np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, :4].sum(axis=1) + 0.1 * rng.randn(n) > 0).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "label": y})

    def one_fit():
        clf = XgboostClassifier(
            n_estimators=trees, max_depth=depth, max_bin=max_bins)
        t0 = time.perf_counter()
        model = clf.fit(df)
        return model, time.perf_counter() - t0

    # Warm fit: XLA compile/trace is not training throughput and
    # stays outside the measured window; the timed reps all hit the
    # in-process jit cache.
    model, warm_fit_s = one_fit()

    # predict is timed PER REP too: a single transform invocation
    # would land in the ledger without samples and face the bare
    # floor in the whole-record verification gate
    fit_samples_s, pred_samples_s = [], []
    for _ in range(max(1, args.reps)):
        model, dt = one_fit()
        fit_samples_s.append(dt)
        t0 = time.perf_counter()
        out = model.transform(df)
        pred_samples_s.append(time.perf_counter() - t0)
    rate_samples = [n * trees / s for s in fit_samples_s]
    pred_s = float(np.percentile(pred_samples_s, 50))
    acc = float((out["prediction"] == df["label"]).mean())
    if acc < 0.6:
        print(json.dumps({"metric": METRIC, "value": None,
                          "error": f"train accuracy collapsed ({acc})"}))
        return 2

    fit_metric = perf.sample_metric(rate_samples, unit=UNIT,
                                    higher_is_better=True, digits=1)
    device_kind = perf.device_kind()
    history = None
    if not args.no_ledger:
        history = perf.append_history(perf.history_record(
            {METRIC: fit_metric,
             "gbdt_predict_rows_per_sec": perf.sample_metric(
                 [n / s for s in pred_samples_s], unit="rows/sec",
                 higher_is_better=True, digits=1)},
            device_kind=device_kind, bench="gbdt_bench",
            extra={"rows": n, "features": f, "trees": trees,
                   "max_depth": depth, "max_bins": max_bins,
                   "tiny": tiny, "warm_fit_sec": round(warm_fit_s, 2)},
        ))

    print(json.dumps({
        "metric": METRIC,
        "value": fit_metric["value"],
        "unit": UNIT,
        "p50": fit_metric["p50"],
        "p99": fit_metric["p99"],
        "rate_samples": fit_metric["samples"],
        "rows": n, "features": f, "trees": trees, "max_depth": depth,
        "max_bins": max_bins, "tiny": tiny,
        "warm_fit_sec": round(warm_fit_s, 2),
        "fit_sec_p50": round(float(np.percentile(fit_samples_s, 50)), 3),
        "predict_sec": round(pred_s, 3),
        "predict_rows_per_sec": round(n / pred_s, 1),
        "train_accuracy": round(acc, 4),
        "device_kind": device_kind,
        "host": perf.host_fingerprint(),
        "history": history,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
