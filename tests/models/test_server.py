"""HTTP front-end: token-id JSON in/out over a live engine — blocking
and SSE-streamed requests, concurrent clients, error paths, and
exactness against the single-stream oracle."""

import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import Llama, LlamaConfig
from sparkdl_tpu.models.generate import generate
from sparkdl_tpu.models.serving import ContinuousBatchingEngine
from sparkdl_tpu.models.server import ServingFrontend


@pytest.fixture(scope="module")
def frontend():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_cache_len=96)
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(model, params, n_slots=2, chunk=4)
    fe = ServingFrontend(eng).start()
    yield fe, cfg, model, params
    fe.close()


def _post(fe, payload):
    req = urllib.request.Request(
        f"http://{fe.address[0]}:{fe.address[1]}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def test_generate_endpoint_matches_oracle(frontend):
    fe, cfg, model, params = frontend
    rng = np.random.default_rng(1)
    p = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    out = _post(fe, {"tokens": p.tolist(), "max_new_tokens": 8})
    oracle = generate(model, params, p[None], max_new_tokens=8,
                      temperature=0.0)
    assert out["tokens"] == np.asarray(oracle)[0, 6:].tolist()
    assert out["finish_reason"] == "length"
    assert len(out["logprobs"]) == 8


def test_concurrent_clients_one_burst(frontend):
    fe, cfg, model, params = frontend
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (5, 7, 9)]
    results = [None] * 3

    def client(i):
        results[i] = _post(fe, {"tokens": prompts[i].tolist(),
                                "max_new_tokens": 6})

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    for i, p in enumerate(prompts):
        oracle = generate(model, params, p[None], max_new_tokens=6,
                          temperature=0.0)
        assert results[i]["tokens"] == \
            np.asarray(oracle)[0, len(p):].tolist()


def test_streaming_sse(frontend):
    fe, cfg, model, params = frontend
    rng = np.random.default_rng(3)
    p = rng.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
    req = urllib.request.Request(
        f"http://{fe.address[0]}:{fe.address[1]}/generate",
        data=json.dumps({"tokens": p.tolist(), "max_new_tokens": 5,
                         "stream": True}).encode(),
    )
    events = []
    with urllib.request.urlopen(req, timeout=300) as r:
        for line in r:
            line = line.strip()
            if line.startswith(b"data: "):
                events.append(json.loads(line[6:]))
    assert events[-1] == {"done": "length"}
    streamed = [e["token"] for e in events[:-1]]
    oracle = generate(model, params, p[None], max_new_tokens=5,
                      temperature=0.0)
    assert streamed == np.asarray(oracle)[0, 5:].tolist()


def test_bad_request_is_400_not_a_hang(frontend):
    fe, *_ = frontend
    # oversized budget: engine.submit raises; the mailbox must carry
    # the error back instead of wedging the client
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(fe, {"tokens": [1, 2, 3], "max_new_tokens": 10_000})
    assert e.value.code == 400
    # malformed body
    with pytest.raises(urllib.error.HTTPError) as e:
        req = urllib.request.Request(
            f"http://{fe.address[0]}:{fe.address[1]}/generate",
            data=b"{not json")
        urllib.request.urlopen(req, timeout=60)
    assert e.value.code == 400


def test_health(frontend):
    fe, *_ = frontend
    with urllib.request.urlopen(
            f"http://{fe.address[0]}:{fe.address[1]}/health",
            timeout=60) as r:
        assert json.loads(r.read())["status"] == "ok"


def test_engine_fault_recovery():
    """A burst that faults must fail ONLY its waiters and leave the
    server healthy: the poison request is aborted out of the engine
    (abort_requests) so the next burst serves normally."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_cache_len=96)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]

    class FaultOnce(ContinuousBatchingEngine):
        faults = [True]

        def _run(self, progress):
            if self.faults:
                self.faults.pop()
                raise RuntimeError("injected fault")
            return super()._run(progress)

    fe = ServingFrontend(FaultOnce(model, params, n_slots=2,
                                   chunk=4)).start()
    try:
        p = np.arange(1, 7, dtype=np.int32)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(fe, {"tokens": p.tolist(), "max_new_tokens": 4})
        # the ENGINE broke on an admitted request: 500, never 400 —
        # the client sent nothing wrong
        assert e.value.code == 500
        assert "engine error" in str(e.value.reason)
        # server recovered: the next request serves correctly
        out = _post(fe, {"tokens": p.tolist(), "max_new_tokens": 4})
        oracle = generate(model, params, p[None], max_new_tokens=4,
                          temperature=0.0)
        assert out["tokens"] == np.asarray(oracle)[0, 6:].tolist()
    finally:
        fe.close()


class _FakeCfg:
    max_cache_len = 64


class _FakeEngine:
    """Engine-shaped stub: lets the handler tests pin the HTTP status
    classification without paying for a model. ``fault`` controls what
    run() does: None = serve, an Exception instance = engine fault
    (500), a BaseException instance = loop death (503)."""

    def __init__(self, fault=None):
        self.cfg = _FakeCfg()
        self.fault = fault
        self.finish_reasons = {}
        self.logprobs = {}
        self._queued = {}
        self._next = 0

    def _worst_case_tokens(self, prompt_len, max_new):
        return prompt_len + max_new

    def submit(self, tokens, max_new_tokens, stop=None):
        rid = self._next
        self._next += 1
        self._queued[rid] = max_new_tokens
        return rid

    def run(self, progress=None, on_token=None):
        if self.fault is not None:
            fault, self.fault = self.fault, None
            raise fault
        out = {}
        for rid, n in self._queued.items():
            toks = np.arange(n, dtype=np.int32)
            if on_token is not None:    # real engines stream per token
                for t in toks:
                    on_token(rid, t)
            out[rid] = toks
            self.finish_reasons[rid] = "length"
            self.logprobs[rid] = [0.0] * n
        self._queued.clear()
        return out

    def abort_requests(self):
        self._queued.clear()


def _post_raw(fe, payload):
    req = urllib.request.Request(
        f"http://{fe.address[0]}:{fe.address[1]}/generate",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=60)


def test_status_classification_400_500_then_recovery():
    """Every fault class on one server: validation 400, engine
    fault 500, then the same server serves 200 (fault recovery)."""
    # multi-line fault text: send_error puts the message on the HTTP
    # status line, so the server must collapse it or the 500 would
    # arrive as a corrupted/split response
    fe = ServingFrontend(_FakeEngine(
        fault=RuntimeError("XLA ate a core\n  backtrace line\n  ünicode"))
    ).start()
    try:
        # request's fault: 400 (budget exceeds max_cache_len)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 1000})
        assert e.value.code == 400
        # engine's fault: 500
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 4})
        assert e.value.code == 500
        assert "engine error: XLA ate a core" in str(e.value.reason)
        assert "\n" not in str(e.value.reason)
        # recovered: 200 with tokens
        with _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 3}) as r:
            assert json.loads(r.read())["tokens"] == [0, 1, 2]
    finally:
        fe.close()


def test_loop_death_fails_waiters_with_503():
    """A dead engine loop (non-Exception escape) must fail waiters
    with 503 — 'retry elsewhere', not 'your request was bad'."""
    fe = ServingFrontend(_FakeEngine(
        fault=KeyboardInterrupt())).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 4})
        assert e.value.code == 503
        assert "shutting down" in str(e.value.reason)
    finally:
        fe.close()


def test_stream_bad_request_is_400_too():
    """The streamed path must reject invalid requests with the SAME
    400 the blocking path gives — never a 200 + SSE error event."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_cache_len=96)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    fe = ServingFrontend(ContinuousBatchingEngine(
        model, params, n_slots=2, chunk=4)).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(fe, {"tokens": [1, 2], "max_new_tokens": 10_000,
                       "stream": True})
        assert e.value.code == 400
        # non-object JSON: 400, not a dropped connection
        with pytest.raises(urllib.error.HTTPError) as e:
            req = urllib.request.Request(
                f"http://{fe.address[0]}:{fe.address[1]}/generate",
                data=json.dumps([1, 2, 3]).encode())
            urllib.request.urlopen(req, timeout=60)
        assert e.value.code == 400
    finally:
        fe.close()


def _get(fe, path):
    with urllib.request.urlopen(
            f"http://{fe.address[0]}:{fe.address[1]}{path}",
            timeout=60) as r:
        return r.headers.get("Content-Type", ""), r.read().decode()


def test_metrics_endpoint_counts_requests_by_class():
    """GET /metrics (ISSUE satellite): request counts per error class,
    queue depth, and request/first-token latency histograms — on the
    fake engine, so the HTTP accounting is pinned without a model."""
    fe = ServingFrontend(_FakeEngine(
        fault=RuntimeError("engine exploded"))).start()
    try:
        # engine's fault first (the fake raises once): 500
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 4})
        assert e.value.code == 500
        # request's fault: 400 (validated before admission)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 1000})
        assert e.value.code == 400
        # two successes (the second streamed)
        with _post_raw(fe, {"tokens": [1, 2], "max_new_tokens": 3}) as r:
            assert json.loads(r.read())["tokens"] == [0, 1, 2]
        with _post_raw(fe, {"tokens": [1], "max_new_tokens": 2,
                            "stream": True}) as r:
            assert b'"done"' in r.read()

        ctype, body = _get(fe, "/metrics")
        assert ctype.startswith("text/plain")
        assert "# TYPE server_requests_total counter" in body
        assert 'server_requests_total{code="200"} 2' in body
        assert 'server_requests_total{code="400"} 1' in body
        assert 'server_requests_total{code="500"} 1' in body
        assert "# TYPE server_queue_depth gauge" in body
        assert "server_queue_depth 0" in body
        # latency histograms: one series per code, counts match
        assert 'server_request_seconds_count{code="200"} 2' in body
        assert 'server_request_seconds_count{code="500"} 1' in body
        # first-token latency observed once per served request
        assert "server_first_token_seconds_count 2" in body
    finally:
        fe.close()


def test_metrics_endpoint_counts_shutdown_503():
    fe = ServingFrontend(_FakeEngine(fault=KeyboardInterrupt())).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1], "max_new_tokens": 2})
        assert e.value.code == 503
        _, body = _get(fe, "/metrics")
        assert 'server_requests_total{code="503"} 1' in body
    finally:
        fe.close()


def test_metrics_endpoint_works_without_telemetry_env(monkeypatch):
    """The serving registry is the frontend's OWN (its /metrics
    endpoint is API surface) — it must serve data even though gang
    telemetry is off by default."""
    monkeypatch.delenv("SPARKDL_TPU_TELEMETRY_DIR", raising=False)
    from sparkdl_tpu import observe
    observe._reset_for_tests()
    try:
        fe = ServingFrontend(_FakeEngine()).start()
        try:
            with _post_raw(fe, {"tokens": [1], "max_new_tokens": 1}) as r:
                r.read()
            _, body = _get(fe, "/metrics")
            assert 'server_requests_total{code="200"} 1' in body
        finally:
            fe.close()
        # ...and none of it leaked into the env-gated global registry
        assert observe.metrics().snapshot()["counters"] == []
    finally:
        observe._reset_for_tests()


def _get_healthz(fe):
    """(status_code, parsed JSON body) — urllib raises on 503, but the
    body is still the JSON probes log."""
    try:
        with urllib.request.urlopen(
                f"http://{fe.address[0]}:{fe.address[1]}/healthz",
                timeout=60) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_healthz_ok_on_live_engine():
    """GET /healthz (ISSUE 5 satellite): 200 with the machine-readable
    liveness triple while the engine loop is up."""
    fe = ServingFrontend(_FakeEngine()).start()
    try:
        code, body = _get_healthz(fe)
        assert code == 200
        assert body == {"status": "ok", "queue_depth": 0,
                        "engine_alive": True}
        # ...and a served request doesn't change liveness
        with _post_raw(fe, {"tokens": [1], "max_new_tokens": 1}) as r:
            r.read()
        assert _get_healthz(fe)[0] == 200
    finally:
        fe.close()


def test_healthz_503_when_engine_loop_dead():
    """A dead engine loop (non-Exception escape — PR 1's lifecycle
    class) must flip /healthz to 503 so a load balancer drains the
    box, with the body saying WHY."""
    import time

    fe = ServingFrontend(_FakeEngine(fault=KeyboardInterrupt())).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post_raw(fe, {"tokens": [1], "max_new_tokens": 2})
        assert e.value.code == 503
        # the loop's finally may still be running: poll briefly
        deadline = time.monotonic() + 10
        code, body = _get_healthz(fe)
        while code != 503 and time.monotonic() < deadline:
            time.sleep(0.05)
            code, body = _get_healthz(fe)
        assert code == 503
        assert body["status"] == "unavailable"
        assert body["engine_alive"] is False
        assert isinstance(body["queue_depth"], int)
    finally:
        fe.close()


def test_healthz_does_not_pollute_request_metrics():
    """Probes hit /healthz every few seconds; they must not show up in
    the request-class counters the SLOs are computed from."""
    fe = ServingFrontend(_FakeEngine()).start()
    try:
        for _ in range(3):
            assert _get_healthz(fe)[0] == 200
        _, body = _get(fe, "/metrics")
        assert "server_requests_total" not in body
    finally:
        fe.close()
