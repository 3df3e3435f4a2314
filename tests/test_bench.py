"""Bench harness machinery tests (cpu, tiny config).

The driver runs ``python bench.py`` and requires exactly one JSON line
on stdout; a backend that never answers must not hang it, so the
bounded-probe orchestration is contract, not decoration.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run(env_extra, timeout=300):
    env = dict(os.environ)
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, BENCH], env=env, capture_output=True,
        text=True, timeout=timeout,
    )


@pytest.mark.gang
def test_bench_emits_single_json_line_on_cpu():
    r = _run({
        "SPARKDL_TPU_BENCH_PLATFORM": "cpu",
        "SPARKDL_TPU_BENCH_TINY": "1",
    })
    assert r.returncode == 0, r.stderr[-800:]
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, r.stdout
    out = json.loads(lines[0])
    assert out["metric"] == "llama_lora_train_tokens_per_sec_per_chip"
    assert out["unit"] == "tokens/sec/chip"
    assert out["value"] > 0
    assert out["vs_baseline"] is not None
    assert 0 <= out["mfu"] < 1
    assert out["platform"] == "cpu"
    # warm-start compilation fields (docs/performance.rst): wall time
    # to a ready executable, and whether the AOT cache served it
    assert out["compile_seconds"] >= 0
    assert out["warm_start"] in (True, False)
    # gang-health fields (docs/observability.rst): steps/sec
    # distribution over repeated invocations of the measured
    # executable (p99 = the slow tail, so p99 <= p50) and the memory
    # high-waters from observe.mem — hbm via the device-stats shim's
    # live-buffer fallback, so it is non-null even on deviceless
    # hosts like this cpu rig, and host RSS always reads
    assert out["steps_per_sec_p50"] > 0
    assert 0 < out["steps_per_sec_p99"] <= out["steps_per_sec_p50"]
    assert out["hbm_high_water_bytes"] > 0
    assert out["host_rss_high_water_bytes"] > 0


@pytest.mark.gang
@pytest.mark.slow   # two full bench subprocesses — outside the tier-1 box
def test_bench_second_run_warm_starts(tmp_path):
    """Two bench runs against one compile-cache dir: the rerun (the
    probe-retry scenario) must deserialize instead of recompiling —
    warm_start flips true and the executable-ready time collapses."""
    env = {
        "SPARKDL_TPU_BENCH_PLATFORM": "cpu",
        "SPARKDL_TPU_BENCH_TINY": "1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
    }
    cold = json.loads(_run(env).stdout.strip().splitlines()[-1])
    warm = json.loads(_run(env).stdout.strip().splitlines()[-1])
    assert cold["warm_start"] is False
    assert warm["warm_start"] is True
    assert warm["compile_seconds"] < cold["compile_seconds"]
    assert warm["last_loss"] == cold["last_loss"]  # same executable


@pytest.mark.gang
@pytest.mark.slow   # a full proxy measurement (~1 min) — outside tier-1
def test_bench_cpu_proxy_on_deviceless_host():
    """ROADMAP item 4 ("un-null the perf trajectory"): a cpu-only run
    WITHOUT the tiny smoke flag measures the fixed-shape CPU proxy and
    reports vs_baseline against the committed CPU baseline — every
    future PR lands a real number on this deviceless container."""
    r = _run({"SPARKDL_TPU_BENCH_PLATFORM": "cpu"}, timeout=600)
    assert r.returncode == 0, r.stderr[-800:]
    lines = [l for l in r.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, r.stdout
    out = json.loads(lines[0])
    assert out["metric"] == "llama_lora_train_tokens_per_sec_cpu_proxy"
    assert out["value"] > 0
    assert out["unit"] == "tokens/sec (cpu proxy)"
    # vs_baseline is computed against the COMMITTED cpu-proxy baseline
    # (BASELINE.json:published), not defaulted to 1.0
    with open(os.path.join(REPO, "BASELINE.json")) as f:
        base = json.load(f)["published"][
            "llama_lora_train_tokens_per_sec_cpu_proxy"]
    assert out["vs_baseline"] == pytest.approx(out["value"] / base,
                                               abs=0.002)
    assert out["platform"] == "cpu"
    assert out["steps_per_sec_p50"] > 0
    # MFU is chip-relative — meaningless for the proxy, so absent
    assert "mfu" not in out


def test_bench_probe_fast_fails_without_accel_devices():
    """ONE probe attempt, never a retry schedule. The explicit bogus
    platform pins the probe failure AND opts out of the cpu-proxy
    fallback, so the bench must report the error quickly."""
    import time

    t0 = time.monotonic()
    r = _run({
        "SPARKDL_TPU_BENCH_PLATFORM": "nosuchplatform",
        "SPARKDL_TPU_BENCH_PROBE_TIMEOUT": "90",
    }, timeout=200)
    elapsed = time.monotonic() - t0
    assert r.returncode != 0
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["value"] is None
    assert "unavailable" in out["error"]
    assert elapsed < 150, f"probe retried ({elapsed:.0f}s)"


def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_mod", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cache_rec(value=1234.5, age_s=60):
    import time

    return {
        "metric": "llama_lora_train_tokens_per_sec_per_chip",
        "value": value,
        "unit": "tokens/sec/chip",
        "vs_baseline": 1.0,
        "platform": "tpu",
        "measured_at": time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime(time.time() - age_s)),
    }


class TestReadCache:
    def test_missing_file_returns_none(self, tmp_path, monkeypatch):
        b = _load_bench()
        monkeypatch.setattr(b, "CACHE_PATH", str(tmp_path / "nope.json"))
        assert b._read_cache() is None

    def test_fresh_record_served_with_advisory_age(self, tmp_path,
                                                   monkeypatch):
        b = _load_bench()
        p = tmp_path / "cache.json"
        p.write_text(json.dumps(_cache_rec(age_s=7200)))
        monkeypatch.setattr(b, "CACHE_PATH", str(p))
        rec = b._read_cache()
        assert rec is not None and rec["value"] == 1234.5
        # the age gate is advisory within the window: the record says
        # how old it is instead of the bench refusing to serve it
        assert 7000 < rec["stale_age_s"] < 7600

    def test_record_older_than_hard_cap_rejected(self, tmp_path,
                                                 monkeypatch):
        b = _load_bench()
        p = tmp_path / "cache.json"
        p.write_text(json.dumps(_cache_rec(age_s=8 * 24 * 3600)))
        monkeypatch.setattr(b, "CACHE_PATH", str(p))
        assert b._read_cache() is None

    def test_wrong_metric_or_null_value_rejected(self, tmp_path,
                                                 monkeypatch):
        b = _load_bench()
        p = tmp_path / "cache.json"
        monkeypatch.setattr(b, "CACHE_PATH", str(p))
        rec = _cache_rec()
        rec["metric"] = "other_metric"
        p.write_text(json.dumps(rec))
        assert b._read_cache() is None
        rec = _cache_rec(value=None)
        p.write_text(json.dumps(rec))
        assert b._read_cache() is None


class TestFailPaths:
    def test_probe_failure_serves_stale_cache_exit_zero(
            self, tmp_path, monkeypatch, capsys):
        b = _load_bench()
        p = tmp_path / "cache.json"
        p.write_text(json.dumps(_cache_rec()))
        monkeypatch.setattr(b, "CACHE_PATH", str(p))
        with pytest.raises(SystemExit) as ei:
            b._fail("backend unavailable", allow_stale=True)
        assert ei.value.code == 0
        out = json.loads(capsys.readouterr().out.strip())
        assert out["value"] == 1234.5
        assert out["stale"] is True
        assert "backend unavailable" in out["stale_reason"]

    def test_run_timeout_never_exits_zero_even_with_cache(
            self, tmp_path, monkeypatch, capsys):
        # ADVICE r3 (high): a hung measured run must not be masked by
        # yesterday's number — the cache may be ATTACHED for context
        # but value stays null and the exit is nonzero.
        b = _load_bench()
        p = tmp_path / "cache.json"
        p.write_text(json.dumps(_cache_rec()))
        monkeypatch.setattr(b, "CACHE_PATH", str(p))
        with pytest.raises(SystemExit) as ei:
            b._fail("measured run timeout", rc=3, attach_cache=True)
        assert ei.value.code == 3
        out = json.loads(capsys.readouterr().out.strip())
        assert out["value"] is None
        assert out["cached_last_good"]["value"] == 1234.5

    def test_probe_failure_without_cache_is_null_nonzero(
            self, tmp_path, monkeypatch, capsys):
        b = _load_bench()
        monkeypatch.setattr(b, "CACHE_PATH", str(tmp_path / "nope.json"))
        with pytest.raises(SystemExit) as ei:
            b._fail("backend unavailable", allow_stale=True)
        assert ei.value.code == 2
        out = json.loads(capsys.readouterr().out.strip())
        assert out["value"] is None


def test_bench_promoted_variant_config(tmp_path):
    """A committed promoted.json redirects the headline measurement
    (fused-CE loss path here) without code changes; the emitted record
    names the promotion."""
    promo = tmp_path / "promoted.json"
    promo.write_text(json.dumps(
        {"attention": "reference", "loss": "fused", "chunk": 64}))
    r = _run({
        "SPARKDL_TPU_BENCH_PLATFORM": "cpu",
        "SPARKDL_TPU_BENCH_TINY": "1",
        "SPARKDL_TPU_BENCH_PROMOTED": str(promo),
    })
    assert r.returncode == 0, r.stderr[-800:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["value"] > 0
    assert out["promoted"]["loss"] == "fused"


def test_bench_promoted_failures_are_loud(tmp_path):
    """A promotion that EXISTS but is broken must fail the bench, not
    silently measure the default config under the promoted label."""
    bad = tmp_path / "promoted.json"
    env_base = {
        "SPARKDL_TPU_BENCH_PLATFORM": "cpu",
        "SPARKDL_TPU_BENCH_TINY": "1",
        "SPARKDL_TPU_BENCH_PROMOTED": str(bad),
    }
    bad.write_text("{not json")
    r = _run(env_base, timeout=120)
    assert r.returncode != 0
    assert "unreadable promoted config" in r.stderr

    bad.write_text(json.dumps({"attention": "falsh"}))  # typo
    r = _run(env_base, timeout=120)
    assert r.returncode != 0
    assert "attention='falsh'" in r.stderr

    bad.write_text(json.dumps({"atention": "flash"}))  # unknown key
    r = _run(env_base, timeout=120)
    assert r.returncode != 0
    assert "unknown promoted.json keys" in r.stderr

    r = _run({**env_base,
              "SPARKDL_TPU_BENCH_PROMOTED": str(tmp_path / "nope.json")},
             timeout=120)
    assert r.returncode != 0
    assert "does not exist" in r.stderr


def test_bench_fails_fast_when_backend_unavailable():
    # an unknown platform name fails backend init on every host; the
    # orchestrator must emit an error JSON line and exit nonzero
    # quickly instead of hanging.
    r = _run({
        "SPARKDL_TPU_BENCH_PLATFORM": "nosuchplatform",
        "SPARKDL_TPU_BENCH_TINY": "1",
        "SPARKDL_TPU_BENCH_PROBE_TIMEOUT": "60",
    }, timeout=200)
    assert r.returncode != 0
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["value"] is None
    assert "unavailable" in out["error"]


class TestPromote:
    """Sweep -> promote -> headline, end to end off-chip: the selection
    logic is code (benchmarks/promote.py), so the untested step of the
    promotion pipeline is no longer a human reading a JSONL."""

    VARIANTS = [
        {"attention": "reference", "batch": 8, "seq": 1024,
         "tokens_per_sec": 90000.0},
        {"attention": "reference", "batch": 8, "seq": 1024,
         "loss": "fused", "chunk": 512, "tokens_per_sec": 99000.0},
        # fastest overall but OFF-SHAPE: must not be promoted
        {"attention": "flash", "batch": 16, "seq": 1024,
         "tokens_per_sec": 120000.0},
        # long-context variant: different workload, ineligible
        {"attention": "flash", "batch": 4, "seq": 4096, "remat": True,
         "tokens_per_sec": 130000.0},
        # error line: swept over, never promoted
        {"attention": "flash", "batch": 8, "seq": 1024,
         "error": "RESOURCE_EXHAUSTED"},
    ]

    def _write_jsonl(self, tmp_path):
        p = tmp_path / "variants.jsonl"
        p.write_text("".join(json.dumps(v) + "\n" for v in self.VARIANTS))
        return p

    def test_picks_fastest_headline_shaped(self, tmp_path):
        sys.path.insert(0, os.path.join(os.path.dirname(BENCH),
                                        "benchmarks"))
        try:
            import promote
        finally:
            sys.path.pop(0)
        best, tps, eligible = promote.pick(self.VARIANTS)
        assert tps == 99000.0
        assert eligible == 2  # the two 8x1024 measured variants
        assert best == {"attention": "reference", "loss": "fused",
                        "chunk": 512}

    @pytest.mark.gang
    def test_promoted_file_drives_the_bench(self, tmp_path):
        jsonl = self._write_jsonl(tmp_path)
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(BENCH), "benchmarks",
                          "promote.py"),
             str(jsonl), "--dry-run"],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode == 0, r.stderr[-400:]
        promo = tmp_path / "promoted.json"
        promo.write_text(r.stdout)
        # bench.py must accept the file promote.py wrote verbatim
        # (contract lock between the two ends of the pipeline)
        b = _run({
            "SPARKDL_TPU_BENCH_PLATFORM": "cpu",
            "SPARKDL_TPU_BENCH_TINY": "1",
            "SPARKDL_TPU_BENCH_PROMOTED": str(promo),
        })
        assert b.returncode == 0, b.stderr[-800:]
        out = json.loads(b.stdout.strip().splitlines()[-1])
        assert out["promoted"] == {"attention": "reference",
                                   "loss": "fused", "chunk": 512}

    def test_no_eligible_variant_fails_loudly(self, tmp_path):
        p = tmp_path / "variants.jsonl"
        p.write_text(json.dumps(
            {"attention": "flash", "batch": 4, "seq": 4096,
             "tokens_per_sec": 1.0}) + "\n")
        r = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(BENCH), "benchmarks",
                          "promote.py"), str(p)],
            capture_output=True, text=True, timeout=60,
        )
        assert r.returncode != 0
        assert "no eligible headline-shaped variant" in r.stderr
