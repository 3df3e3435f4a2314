"""Multi-replica fleet frontend: admission control, load-aware
routing, and failure routing across >1 replica (ISSUE 11).

The contract under test: a replica that dies mid-stream fails its
in-flight requests with 500 (never hangs them), subsequent arrivals
route to survivors, a hung replica is drained and REPLACED, overload
answers a fast 503, and ``server_requests_total{code=...}`` accounts
every single outcome."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.models import Llama, LlamaConfig
from sparkdl_tpu.models.fleet import EngineWorker, FleetFrontend
from sparkdl_tpu.models.generate import generate
from sparkdl_tpu.models.serving import ContinuousBatchingEngine


class _FakeCfg:
    max_cache_len = 64


class _FakeEngine:
    """Engine-shaped stub (the test_server pattern): serves
    arange(max_new) per request. ``fault`` = Exception → engine fault
    (recoverable 500); BaseException → loop death; ``block`` = an
    Event the engine waits on inside run() (a hung replica)."""

    def __init__(self, fault=None, block=None, delay=0.0):
        self.cfg = _FakeCfg()
        self.fault = fault
        self.block = block
        self.entered = threading.Event()   # set once run() is waiting
        self.delay = delay
        self.telemetry = None
        self.finish_reasons = {}
        self.logprobs = {}
        self._queued = {}
        self._next = 0
        self.served = 0

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
        if self.block is not None:
            self.entered.set()
            self.block.wait()
        out = {}
        for rid, n in self._queued.items():
            if self.telemetry is not None:
                self.telemetry.request_admitted(rid)
            if self.delay:
                time.sleep(self.delay)
            toks = np.arange(n, dtype=np.int32)
            if on_token is not None:
                for t in toks:
                    on_token(rid, t)
            out[rid] = toks
            self.finish_reasons[rid] = "length"
            self.logprobs[rid] = [0.0] * n
            self.served += 1
        self._queued.clear()
        return out

    def abort_requests(self):
        self._queued.clear()


def _url(fleet, path="/generate"):
    return f"http://{fleet.address[0]}:{fleet.address[1]}{path}"


def _post(fleet, payload, timeout=60):
    req = urllib.request.Request(
        _url(fleet), data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(fleet, path, timeout=30):
    with urllib.request.urlopen(_url(fleet, path), timeout=timeout) as r:
        return r.status, r.read()


def _requests_total(fleet):
    """{code: count} from the fleet registry."""
    out = {}
    for (name, labels), c in fleet.metrics._metrics.items():
        if name == "server_requests_total":
            out[dict(labels)["code"]] = c.value
    return out


def _wait_until(reached, what, timeout=30):
    """Poll ``reached()``; ``what()`` is shown if it never comes."""
    deadline = time.monotonic() + timeout
    while not reached():
        assert time.monotonic() < deadline, what()
        time.sleep(0.01)


def _fake_fleet(factory, **kw):
    kw.setdefault("poll_seconds", 0.05)
    kw.setdefault("hang_seconds", 60.0)
    return FleetFrontend(factory, **kw).start()


def test_fleet_serves_and_routes_by_depth():
    """Requests land on the least-loaded live replica; all complete."""
    engines = []

    def factory():
        e = _FakeEngine()
        engines.append(e)
        return e

    fleet = _fake_fleet(factory, replicas=2, max_queue=32)
    try:
        for _ in range(8):
            out = _post(fleet, {"tokens": [1, 2], "max_new_tokens": 3})
            assert out["tokens"] == [0, 1, 2]
        assert sum(e.served for e in engines) == 8
        # a handler counts its request after it has answered: the last
        # count can trail the client's return
        _wait_until(lambda: _requests_total(fleet) == {"200": 8},
                    lambda: _requests_total(fleet))
    finally:
        fleet.close()


def test_admission_control_rejects_503_above_bound():
    """Arrivals above max_queue get a fast 503 (+ Retry-After), are
    counted as rejections, and NEVER hang; the fleet keeps serving
    after the burst."""
    gate = threading.Event()
    engines = []

    def factory():
        engines.append(_FakeEngine(block=gate))
        return engines[-1]

    fleet = _fake_fleet(factory, replicas=1, max_queue=2)
    try:
        results = []

        def client():
            try:
                results.append(
                    ("ok", _post(fleet, {"tokens": [1],
                                         "max_new_tokens": 2})))
            except urllib.error.HTTPError as e:
                results.append((e.code, dict(e.headers)))

        threads = [threading.Thread(target=client) for _ in range(6)]
        # Fill the bound and WAIT for it: once the engine sits in
        # run() behind the gate nothing leaves the queue, so the depth
        # the frontend exports can only grow until the gate opens.
        for t in threads[:2]:
            t.start()
        assert engines[0].entered.wait(timeout=30)
        _wait_until(lambda: fleet.queue_depth() >= 2, fleet.queue_depth)
        # the bound is full and held: every later arrival is refused
        for t in threads[2:]:
            t.start()
        for t in threads[2:]:
            t.join(timeout=30)
        gate.set()
        for t in threads[:2]:
            t.join(timeout=30)
        codes = [r[0] for r in results]
        assert codes.count("ok") == 2, codes
        rejected = [r for r in results if r[0] == 503]
        assert len(rejected) == 4, codes
        assert all(h.get("Retry-After") == "1" for _, h in rejected)
        # every outcome accounted, nothing lost (a handler counts its
        # request after it has answered, so the last count can trail
        # the client's return)
        _wait_until(lambda: sum(_requests_total(fleet).values()) >= 6,
                    lambda: _requests_total(fleet))
        counts = _requests_total(fleet)
        assert sum(counts.values()) == 6
        assert counts.get("503", 0) == len(rejected)
        rej = fleet.metrics.counter(
            "server_admission_rejections_total", reason="overload")
        assert rej.value == len(rejected)
    finally:
        gate.set()
        fleet.close()


def test_replica_death_fails_in_flight_500_and_survivors_serve():
    """The satellite-4 contract: a replica that dies mid-burst fails
    its in-flight requests with 500 (not a hang), later arrivals
    route to the survivor, and the restart counter fires."""
    made = []

    def factory():
        # first engine dies on its first run(); every later engine
        # (the survivor + the respawn) serves normally
        e = _FakeEngine(
            fault=SystemExit("injected death") if not made else None)
        made.append(e)
        return e

    fleet = _fake_fleet(factory, replicas=2, max_queue=32)
    try:
        # pin the first request onto the doomed replica 0 (both are
        # idle, the router picks min depth = first in list)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(fleet, {"tokens": [1, 2], "max_new_tokens": 4})
        assert e.value.code == 500
        assert "died" in str(e.value.reason)
        # survivors absorb traffic (and the supervisor respawns the
        # dead replica within a poll or two)
        for _ in range(4):
            out = _post(fleet, {"tokens": [1], "max_new_tokens": 2})
            assert out["tokens"] == [0, 1]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if fleet.metrics.counter("server_replica_restarts_total",
                                     cause="death").value >= 1:
                break
            time.sleep(0.05)
        assert fleet.metrics.counter(
            "server_replica_restarts_total", cause="death").value >= 1
        counts = _requests_total(fleet)
        assert counts.get("500") == 1 and counts.get("200") == 4
        assert sum(counts.values()) == 5
    finally:
        fleet.close()


def test_replica_death_mid_stream_ends_sse_with_error_event():
    """A streaming client of a dying replica gets a terminal error
    event (the SSE already committed 200), never a hang."""
    def factory():
        return _FakeEngine(fault=SystemExit("injected death"))

    fleet = _fake_fleet(factory, replicas=1, max_queue=8,
                        respawn=False)
    try:
        req = urllib.request.Request(
            _url(fleet),
            data=json.dumps({"tokens": [1], "max_new_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        events = []
        with urllib.request.urlopen(req, timeout=30) as r:
            for line in r:
                line = line.strip()
                if line.startswith(b"data: "):
                    events.append(json.loads(line[6:]))
        assert events and "error" in events[-1]
        assert "died" in events[-1]["error"]
        counts = _requests_total(fleet)
        assert counts.get("500") == 1
    finally:
        fleet.close()


def test_hung_replica_is_drained_and_replaced():
    """A replica with work but no progress past hang_seconds: its
    waiter gets 500 (not a hang), a fresh replica takes its slot, and
    the fleet serves on."""
    gate = threading.Event()
    made = []

    def factory():
        e = _FakeEngine(block=None if made else gate)
        made.append(e)
        return e

    fleet = _fake_fleet(factory, replicas=1, max_queue=8,
                        hang_seconds=0.4, poll_seconds=0.05)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(fleet, {"tokens": [1], "max_new_tokens": 2},
                  timeout=30)
        assert e.value.code == 500
        assert "hung" in str(e.value.reason)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            states = fleet.replica_states()
            if states and states[0]["alive"]:
                break
            time.sleep(0.05)
        out = _post(fleet, {"tokens": [1], "max_new_tokens": 2})
        assert out["tokens"] == [0, 1]
        assert fleet.metrics.counter(
            "server_replica_restarts_total", cause="hang").value == 1
    finally:
        gate.set()
        fleet.close()


def test_healthz_fleet_and_metrics_surfaces():
    def factory():
        return _FakeEngine()

    fleet = _fake_fleet(factory, replicas=2, max_queue=4)
    try:
        status, body = _get(fleet, "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["replicas_alive"] == 2
        _, body = _get(fleet, "/fleet")
        doc = json.loads(body)
        assert [r["replica"] for r in doc["replicas"]] == [0, 1]
        assert doc["max_queue"] == 4
        _post(fleet, {"tokens": [1], "max_new_tokens": 2})
        _, body = _get(fleet, "/metrics")
        prom = body.decode()
        for series in ("server_requests_total", "server_queue_depth",
                       "server_replicas_alive",
                       "server_replica_queue_depth"):
            assert series in prom, series
    finally:
        fleet.close()
    # draining fleet answers 503 on healthz
    status = None
    try:
        urllib.request.urlopen(_url(fleet, "/healthz"), timeout=5)
    except (urllib.error.HTTPError, urllib.error.URLError) as e:
        status = getattr(e, "code", "closed")
    assert status in (503, "closed")


def test_bad_request_400_even_when_saturated():
    """Admission control must not reclassify malformed input: a junk
    body is 400, not 503, even with the queue full."""
    gate = threading.Event()

    def factory():
        return _FakeEngine(block=gate)

    fleet = _fake_fleet(factory, replicas=1, max_queue=1)
    try:
        t = threading.Thread(
            target=lambda: _post(fleet, {"tokens": [1],
                                         "max_new_tokens": 2}))
        t.start()
        time.sleep(0.2)   # saturate the bound
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(fleet, {"tokens": "junk"})
        assert e.value.code == 400
        gate.set()
        t.join(timeout=30)
    finally:
        gate.set()
        fleet.close()


@pytest.mark.slow
def test_fleet_real_engines_match_oracle_and_mixed_quant():
    """End to end with REAL engines: a 2-replica fleet (one bf16, one
    int8 replica off the same checkpoint) serves correct tokens —
    int8 replicas answer with the quantized model's greedy decode, so
    the fleet here is homogeneous-bf16 for the oracle check, then a
    second homogeneous-int8 fleet is checked against the int8 oracle."""
    cfg = LlamaConfig.tiny(dtype=jnp.float32, max_cache_len=96)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    p = np.arange(1, 7, dtype=np.int32)

    for quant in ("", "int8"):
        def factory():
            return ContinuousBatchingEngine(
                model, params, n_slots=2, chunk=4, quant=quant)

        if quant:
            import dataclasses

            from sparkdl_tpu.models.quant import quantize_llama_params

            oracle_model = Llama(dataclasses.replace(cfg, quant=quant))
            oracle_params = quantize_llama_params(params)
        else:
            oracle_model, oracle_params = model, params
        oracle = np.asarray(generate(
            oracle_model, oracle_params, p[None], max_new_tokens=5,
            temperature=0.0))[0, 6:]
        fleet = FleetFrontend(factory, replicas=2,
                              max_queue=16).start()
        try:
            outs = []
            threads = [threading.Thread(target=lambda: outs.append(
                _post(fleet, {"tokens": p.tolist(),
                              "max_new_tokens": 5},
                      timeout=300)["tokens"]))
                for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert len(outs) == 4
            for o in outs:
                assert o == oracle.tolist()
        finally:
            fleet.close()


def test_hang_detected_under_sustained_traffic():
    """Arrivals keep flowing at a wedged replica: the hang clock must
    NOT reset per submit (only an idle worker's first arrival does),
    so the verdict still lands within ~hang_seconds and every parked
    client gets its 500."""
    gate = threading.Event()
    made = []

    def factory():
        e = _FakeEngine(block=None if made else gate)
        made.append(e)
        return e

    fleet = _fake_fleet(factory, replicas=1, max_queue=32,
                        hang_seconds=0.5, poll_seconds=0.05)
    try:
        results = []

        def client():
            try:
                _post(fleet, {"tokens": [1], "max_new_tokens": 2},
                      timeout=30)
                results.append("ok")
            except urllib.error.HTTPError as e:
                results.append(e.code)

        threads = []
        t_start = time.monotonic()
        # a steady drip faster than hang_seconds for ~3x the window
        for _ in range(15):
            t = threading.Thread(target=client)
            t.start()
            threads.append(t)
            time.sleep(0.1)
            if fleet.metrics.counter("server_replica_restarts_total",
                                     cause="hang").value:
                break
        verdict_at = time.monotonic() - t_start
        gate.set()
        for t in threads:
            t.join(timeout=30)
        assert fleet.metrics.counter(
            "server_replica_restarts_total", cause="hang").value >= 1, \
            f"no hang verdict under sustained traffic ({results})"
        # the verdict must land near the window, not after the drip
        # ends (pre-fix behavior: every submit deferred it)
        assert verdict_at < 1.4, verdict_at
        assert 500 in results
    finally:
        gate.set()
        fleet.close()


def test_simultaneous_burst_spreads_across_replicas():
    """Routing happens under the admission lock, so a burst of
    concurrent arrivals sees each other's enqueues: with blocked
    engines, a 6-request burst at a 2-replica fleet must land 3/3 —
    not all on replica 0 (the pre-lock-routing failure mode)."""
    gate = threading.Event()
    engines = []

    def factory():
        e = _FakeEngine(block=gate)
        engines.append(e)
        return e

    fleet = _fake_fleet(factory, replicas=2, max_queue=32)
    try:
        threads = [threading.Thread(
            target=lambda: _post(fleet, {"tokens": [1],
                                         "max_new_tokens": 2}))
            for _ in range(6)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            depths = [s["depth"] for s in fleet.replica_states()]
            if sum(depths) == 6:
                break
            time.sleep(0.02)
        assert sorted(depths) == [3, 3], depths
        gate.set()
        for t in threads:
            t.join(timeout=30)
    finally:
        gate.set()
        fleet.close()


def test_last_progress_writes_go_through_the_worker_lock():
    """Regression (analysis.concur unguarded-shared-state):
    last_progress is written by the engine thread (chunks, tokens,
    queue polls) AND handler threads (idle-arrival reset in submit),
    and read by the supervisor's hung() — every write must go through
    _touch_progress() under the worker lock."""
    from sparkdl_tpu.observe.metrics import Registry

    w = EngineWorker(0, _FakeEngine, Registry())
    before = w.last_progress
    # _touch_progress takes the lock itself; with the lock held by
    # another party, an unguarded write would have raced straight
    # through — the guarded one must wait, proving the stamp is
    # serialized with _lock.
    acquired = w._lock.acquire()
    assert acquired
    t = threading.Thread(target=w._touch_progress)
    t.start()
    t.join(timeout=0.2)
    assert t.is_alive()                 # blocked on the worker lock
    assert w.last_progress == before    # no torn write slipped through
    w._lock.release()
    t.join(timeout=5)
    assert not t.is_alive()
    assert w.last_progress > before
    # the telemetry hook stamps through the same guarded path
    mid = w.last_progress
    w.engine.telemetry.decode_chunk(active=1, n_slots=1, n_tokens=1)
    assert w.last_progress >= mid
