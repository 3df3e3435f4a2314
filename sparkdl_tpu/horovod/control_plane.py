"""Worker→driver control plane for HorovodRunner gangs.

The reference defers this entire subsystem to Databricks Runtime and only
fixes its observable behavior: a worker→driver string log channel with
4000-char truncation (reference ``sparkdl/horovod/__init__.py:20-25``),
a log routing policy keyed on ``driver_log_verbosity`` (reference
``runner_base.py:62-72``), and cloudpickled rank-0 return-value shipping
(reference ``runner_base.py:93-95``). This module implements that
control plane for real: a threaded TCP server on the driver and a
framed-message client in each worker.

Design notes (TPU-first): the *data plane* — gradients, parameters,
collectives — never touches this channel; it rides XLA collectives over
ICI/DCN inside jitted programs (see :mod:`sparkdl_tpu.hvd`). The control
plane only carries low-rate strings and the one-shot result blob, so a
simple length-prefixed TCP protocol is sufficient and keeps worker step
time unaffected (contract: "all" verbosity must not stall training,
reference ``runner_base.py:65-68`` — log sends here are fire-and-forget
writes to a socket buffer from the logging thread).

Frame format: ``u32 length | u8 type | u32 rank | payload`` (big endian).
JSON payloads for control messages; raw cloudpickle bytes for RESULT.
"""

import hashlib
import hmac
import json
import os
import secrets as _secrets
import socket
import struct
import threading
import time

# Message types
MSG_READY = 1
MSG_LOG = 2
MSG_USERLOG = 3
MSG_RESULT = 4
MSG_EXC = 5
MSG_BYE = 6
MSG_AUTH = 7
MSG_RESULT_PART = 8   # chunk of an oversized RESULT (rank 0 only)
MSG_RESULT_END = 9    # terminates a chunked RESULT
MSG_TELEMETRY = 10    # observe: batched metric snapshot + timeline events
MSG_HEARTBEAT = 11    # observe.health: per-rank liveness beacon
MSG_DUMP_REQ = 12     # driver→worker: send an all-thread stack dump
MSG_STACK_DUMP = 13   # worker→driver: the faulthandler dump text
MSG_PROFILE_REQ = 14  # driver→worker: capture a perf-forensics window
MSG_PROFILE_DONE = 15  # worker→driver: capture finished (report meta)
MSG_LAUNCH = 16       # worker→driver: its launch-record spans (always on)

_HEADER = struct.Struct(">IBI")  # length (of type+rank+payload), type, rank

# Frame names for the chaos harness's drop/delay selectors
# (SPARKDL_TPU_CHAOS_CP_DROP names frames by these strings).
_MSG_NAMES = {
    MSG_READY: "READY", MSG_LOG: "LOG", MSG_USERLOG: "USERLOG",
    MSG_RESULT: "RESULT", MSG_EXC: "EXC", MSG_BYE: "BYE",
    MSG_AUTH: "AUTH", MSG_RESULT_PART: "RESULT", MSG_RESULT_END: "RESULT",
    MSG_TELEMETRY: "TELEMETRY", MSG_HEARTBEAT: "HEARTBEAT",
    MSG_STACK_DUMP: "STACK_DUMP", MSG_PROFILE_REQ: "PROFILE_REQ",
    MSG_PROFILE_DONE: "PROFILE_DONE", MSG_LAUNCH: "LAUNCH",
}

CONTROL_ADDR_ENV = "SPARKDL_TPU_CONTROL_ADDR"
RANK_ENV = "SPARKDL_TPU_RANK"
CONTROL_SECRET_ENV = "SPARKDL_TPU_CONTROL_SECRET"

# The driver cloudpickle.loads() the RESULT payload, so an attacker who
# can deliver frames can execute code on the driver. Every connection
# must therefore open with an AUTH frame proving knowledge of the
# per-job secret (distributed to workers via the job env, never over
# the wire). A frame-length cap bounds allocation from untrusted peers.
# Threat model: peers WITHOUT the job secret. Gang workers hold the
# shared secret and are trusted — any of them could derive another
# rank's token; the per-connection rank pinning below catches bugs and
# misrouted frames, not a malicious worker.
MAX_FRAME = 64 << 20

# RESULTs bigger than one frame (e.g. returned model weights) ship as
# MSG_RESULT_PART chunks + MSG_RESULT_END, reassembled on the driver
# up to a separate (authenticated, rank-0-only) total cap.
RESULT_CHUNK = 32 << 20
MAX_RESULT_TOTAL = int(
    os.environ.get("SPARKDL_TPU_MAX_RESULT_BYTES", str(4 << 30))
)


def auth_token(secret, rank):
    """Per-rank connection credential: HMAC-SHA256 over the rank so the
    raw job secret never crosses the wire."""
    return hmac.new(
        secret.encode("utf-8"),
        b"sparkdl-tpu-auth-v1" + struct.pack(">I", rank),
        hashlib.sha256,
    ).digest()


def auth_frame(secret, rank):
    """The complete wire frame a client must send first on connect."""
    token = auth_token(secret, rank)
    return _HEADER.pack(len(token) + 5, MSG_AUTH, rank) + token


_AUTH_FRAME_LEN = len(auth_frame("", 0))  # fixed size: header + HMAC-SHA256

# Guard against a runaway worker flooding the driver (backpressure
# contract, reference runner_base.py:65-68): log text is truncated by
# the sender BEFORE JSON-encoding (truncating the encoded frame would
# produce invalid JSON and poison the connection).
MAX_LOG_TEXT = 64 << 10


def routable_host_ip():
    """Best-effort routable IP of this host (UDP-connect trick —
    ``gethostbyname(gethostname())`` resolves to 127.0.1.1 on stock
    Debian-style /etc/hosts, which would point remote workers at their
    own loopback)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect(("10.255.255.255", 1))  # no packets sent
        return s.getsockname()[0]
    except OSError:
        return socket.gethostbyname(socket.gethostname())
    finally:
        s.close()


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


class ControlPlaneServer:
    """Driver-side server: merges worker logs, routes them per the
    verbosity policy, and collects the rank-0 result.

    Log routing (reference ``runner_base.py:62-72``): every worker LOG
    line is merged into ``log_path`` (the analogue of "merged into the
    first executor's stderr"); with ``verbosity="all"`` each line is
    additionally streamed to the driver's stdout; with the default
    ``"log_callback_only"`` only USERLOG messages (sent via
    ``log_to_driver``) are printed.
    """

    def __init__(self, num_workers, verbosity="log_callback_only", log_path=None,
                 bind_host="127.0.0.1", advertise_host=None, secret=None,
                 telemetry=None, health=None, on_launch_spans=None):
        self.num_workers = num_workers
        self.verbosity = verbosity
        # Where LAUNCH frames go: callable(rank, [span dict, ...]) —
        # the launcher hands each worker's lifecycle spans to the
        # driver's launch record (sparkdl_tpu.observe.launch), which
        # shape-checks them; without one they are dropped.
        self._on_launch_spans = on_launch_spans
        # Optional observability sink (sparkdl_tpu.observe.aggregate.
        # GangTelemetry): TELEMETRY frames are decoded and handed to
        # it; without one they are dropped (telemetry is opt-in).
        self._telemetry = telemetry
        # Optional hang detector (sparkdl_tpu.observe.health.
        # HangDetector): HEARTBEAT frames feed it; without one they
        # are dropped (health is part of the same telemetry opt-in).
        self._health = health
        # rank -> the connection carrying that rank's GUARANTEED
        # control socket (recorded on READY/HEARTBEAT — the native log
        # sender's extra connections only ever carry LOG and have no
        # reader on the worker side, so a driver→worker dump request
        # must ride the main socket the watchdog reads).
        self._conns = {}
        self._stack_dumps = {}  # rank -> [dump text, ...]
        self._profile_reports = {}  # rank -> [report meta dict, ...]
        # Optional observer for PROFILE_DONE frames (the forensics
        # manager clears its in-flight latch here); called OUTSIDE the
        # server lock with (rank, report_meta_dict).
        self.on_profile_done = None
        # Per-job shared secret; the launcher ships it to workers via
        # CONTROL_SECRET_ENV. Auto-generated so no caller can forget it.
        self.secret = secret or _secrets.token_hex(32)
        self.log_path = log_path
        self._log_file = open(log_path, "a", buffering=1) if log_path else None
        self._lock = threading.Lock()
        self._ready = set()
        self._done = set()
        self._result = None
        self._result_rank = None
        self._result_parts = []
        self._result_parts_bytes = 0
        self._result_overflow = False
        self._exceptions = {}  # rank -> traceback string
        self._exit_codes = {}
        self._ready_cond = threading.Condition(self._lock)
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((bind_host, 0))
        self._srv.listen(max(num_workers, 8))
        port = self._srv.getsockname()[1]
        if advertise_host is None:
            # When bound to all interfaces (cluster mode), advertise a
            # routable address — loopback would point remote workers at
            # themselves.
            advertise_host = (
                routable_host_ip() if bind_host == "0.0.0.0" else bind_host
            )
        self.address = f"{advertise_host}:{port}"
        self._closed = False
        self._threads = []
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="sparkdl-tpu-control-accept", daemon=True
        )
        self._accept_thread.start()

    # -- server internals ---------------------------------------------------

    def _accept_loop(self):
        while not self._closed:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            t = threading.Thread(
                target=self._serve_conn, args=(conn,),
                name="sparkdl-tpu-control-conn", daemon=True,
            )
            t.start()
            # _threads is read by wait_drained() from the driver
            # thread while this accept thread appends: share it under
            # the lock, and prune finished handlers so a chatty gang
            # (reconnects, per-attempt clients) cannot grow the list
            # for the life of the server.
            with self._lock:
                self._threads = [
                    x for x in self._threads if x.is_alive()
                ]
                self._threads.append(t)

    def _log_server_event(self, text):
        with self._lock:
            if self._log_file is not None:
                self._log_file.write(f"[control-plane] {text}\n")

    def _serve_conn(self, conn):
        auth_rank = None  # rank proven by the AUTH handshake
        auth_len = _AUTH_FRAME_LEN - _HEADER.size
        try:
            while True:
                head = _recv_exact(conn, _HEADER.size)
                if head is None:
                    return
                length, mtype, rank = _HEADER.unpack(head)
                if auth_rank is None and length - 5 != auth_len:
                    # Pre-auth, the ONLY legal frame is the fixed-size
                    # AUTH frame — an unauthenticated peer must not be
                    # able to make us buffer anything bigger.
                    self._log_server_event(
                        f"pre-auth frame with length {length}; closing"
                    )
                    return
                if length < 5 or length - 5 > MAX_FRAME:
                    # Bounded allocation from untrusted peers: drop the
                    # connection rather than trust a u32 length.
                    self._log_server_event(
                        f"oversized frame ({length} bytes) from rank "
                        f"{rank}; closing connection"
                    )
                    return
                payload = _recv_exact(conn, length - 5)
                if payload is None:
                    return
                if auth_rank is None:
                    # First frame MUST be a valid AUTH; anything else —
                    # including a bad token — closes the connection
                    # before a single byte reaches the handlers.
                    if mtype != MSG_AUTH or not hmac.compare_digest(
                        payload, auth_token(self.secret, rank)
                    ):
                        self._log_server_event(
                            f"unauthenticated connection (first frame "
                            f"type {mtype}, claimed rank {rank}); closing"
                        )
                        return
                    auth_rank = rank
                    continue
                if mtype == MSG_AUTH:
                    continue  # re-auth is a no-op
                if rank != auth_rank:
                    # The per-rank HMAC binds the connection to ONE
                    # rank; a frame claiming another rank is a protocol
                    # violation (a bug or misrouted frame — see the
                    # threat-model note on MAX_FRAME: this does not
                    # defend against a malicious secret-holding worker).
                    self._log_server_event(
                        f"rank-{auth_rank} connection sent a frame "
                        f"claiming rank {rank}; closing"
                    )
                    return
                if mtype in (MSG_READY, MSG_HEARTBEAT):
                    # This connection is the rank's guaranteed control
                    # socket (its worker runs the watchdog reader on
                    # it) — the channel driver→worker dump requests
                    # ride. Native log connections never send these.
                    with self._lock:
                        self._conns[rank] = conn
                try:
                    self._handle(mtype, rank, payload)
                except Exception:
                    # A malformed frame must not kill the connection —
                    # READY/RESULT/BYE from this rank still need to
                    # arrive. Log and keep serving.
                    import traceback

                    with self._lock:
                        if self._log_file is not None:
                            self._log_file.write(
                                f"[control-plane] bad frame from rank {rank}:\n"
                                f"{traceback.format_exc()}\n"
                            )
        except OSError:
            pass
        finally:
            conn.close()

    def _handle(self, mtype, rank, payload):
        if mtype == MSG_READY:
            with self._ready_cond:
                self._ready.add(rank)
                self._ready_cond.notify_all()
        elif mtype == MSG_LOG:
            msg = json.loads(payload.decode("utf-8", "replace"))
            line = msg.get("text", "")
            stream = msg.get("stream", "stdout")
            with self._lock:
                if self._log_file is not None:
                    self._log_file.write(f"[rank {rank} {stream}] {line}\n")
            if self.verbosity == "all":
                print(f"[{rank}] {line}", flush=True)
        elif mtype == MSG_USERLOG:
            msg = json.loads(payload.decode("utf-8", "replace"))
            # log_to_driver contract: driver prints to stdout
            # (reference sparkdl/horovod/__init__.py:20-25).
            print(msg.get("text", ""), flush=True)
            with self._lock:
                if self._log_file is not None:
                    self._log_file.write(f"[rank {rank} log_to_driver] {msg.get('text', '')}\n")
        elif mtype in (MSG_RESULT, MSG_RESULT_PART, MSG_RESULT_END):
            if rank != 0:
                # The contract returns rank 0's value only (reference
                # runner_base.py:93-95); a RESULT from any other rank is
                # a protocol violation, not data.
                self._log_server_event(
                    f"ignoring RESULT from rank {rank} (only rank 0 may "
                    "return the job value)"
                )
                return
            if mtype == MSG_RESULT:
                with self._lock:
                    self._result = payload
                    self._result_rank = rank
            elif mtype == MSG_RESULT_PART:
                with self._lock:
                    if self._result_overflow:
                        return
                    self._result_parts.append(payload)
                    self._result_parts_bytes += len(payload)
                    if self._result_parts_bytes > MAX_RESULT_TOTAL:
                        # Bound driver memory even for the trusted path;
                        # the job then surfaces "no result" with this
                        # line in the job log explaining why.
                        self._result_overflow = True
                        self._result_parts = []
                        self._result_parts_bytes = 0
                if self._result_overflow:
                    self._log_server_event(
                        "chunked RESULT exceeded "
                        f"{MAX_RESULT_TOTAL} bytes; discarded (raise "
                        "SPARKDL_TPU_MAX_RESULT_BYTES if the return "
                        "value is legitimately this large)"
                    )
            else:  # MSG_RESULT_END
                with self._lock:
                    if not self._result_overflow:
                        self._result = b"".join(self._result_parts)
                        self._result_rank = rank
                    self._result_parts = []
                    self._result_parts_bytes = 0
        elif mtype == MSG_TELEMETRY:
            if self._telemetry is not None:
                # ingest() shape-checks and raises on malformed frames;
                # the per-frame handler above logs and keeps serving,
                # so bad telemetry can never poison READY/RESULT/BYE.
                self._telemetry.ingest(
                    rank, json.loads(payload.decode("utf-8", "replace"))
                )
        elif mtype == MSG_LAUNCH:
            if self._on_launch_spans is not None:
                self._on_launch_spans(
                    rank, json.loads(payload.decode("utf-8", "replace")))
        elif mtype == MSG_HEARTBEAT:
            if self._health is not None:
                self._health.observe_beat(
                    rank, json.loads(payload.decode("utf-8", "replace"))
                )
        elif mtype == MSG_STACK_DUMP:
            msg = json.loads(payload.decode("utf-8", "replace"))
            dump = str(msg.get("dump", ""))
            with self._lock:
                self._stack_dumps.setdefault(rank, []).append(dump)
                if self._log_file is not None:
                    self._log_file.write(
                        f"[rank {rank} STACK DUMP "
                        f"({msg.get('reason', 'requested')})]\n{dump}\n"
                    )
            if self._telemetry is not None:
                self._telemetry.add_stack_dump(
                    rank, dump, reason=msg.get("reason")
                )
            if self._health is not None:
                self._health.note_stack_dump(rank)
        elif mtype == MSG_PROFILE_DONE:
            msg = json.loads(payload.decode("utf-8", "replace"))
            if not isinstance(msg, dict):
                msg = {}
            with self._lock:
                self._profile_reports.setdefault(rank, []).append(msg)
                if self._log_file is not None:
                    self._log_file.write(
                        f"[rank {rank} PROFILE DONE "
                        f"({msg.get('reason', 'requested')}) "
                        f"{msg.get('report') or ''}]\n"
                    )
            cb = self.on_profile_done
            if cb is not None:
                # outside the lock: the forensics manager takes its own
                cb(rank, msg)
        elif mtype == MSG_EXC:
            msg = json.loads(payload.decode("utf-8", "replace"))
            with self._lock:
                self._exceptions[rank] = msg.get("traceback", "")
                if self._log_file is not None:
                    self._log_file.write(f"[rank {rank} EXCEPTION]\n{msg.get('traceback', '')}\n")
        elif mtype == MSG_BYE:
            msg = json.loads(payload.decode("utf-8", "replace"))
            with self._ready_cond:
                self._done.add(rank)
                self._exit_codes[rank] = msg.get("exit_code", 0)
                self._ready_cond.notify_all()

    # -- driver-facing API --------------------------------------------------

    def wait_ready(self, timeout):
        """Gang barrier: wait until all workers report READY.

        Fail-fast semantics per the contract "np tasks starting all
        together" / fail if slots unavailable (reference
        ``runner_base.py:54-58``): returns False on timeout.
        """
        deadline = time.monotonic() + timeout
        with self._ready_cond:
            while len(self._ready) < self.num_workers:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._ready_cond.wait(remaining)
        return True

    def wait_drained(self, timeout=5.0):
        """Join connection handlers so every frame already on the wire
        is processed. Workers' sockets hit EOF when their processes
        exit, and TCP delivers all buffered bytes before EOF — so once
        the handler threads finish, no log line can arrive late (the
        tail-of-job guarantee behind the 'all'-verbosity contract)."""
        deadline = time.monotonic() + timeout
        with self._lock:
            threads = list(self._threads)
        # join OUTSIDE the lock: handlers take it to record results,
        # and a join-under-lock would deadlock the drain.
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))

    def request_dump(self, rank, reason="stall"):
        """Ask ``rank`` for an all-thread stack dump (hang/straggler
        diagnosis). Sent down the rank's guaranteed control socket,
        where the worker's driver-watchdog reader answers with a
        ``STACK_DUMP`` frame. Returns False when the rank has no
        registered connection (never beat/READY'd) or the send fails —
        a diagnosis request must never raise into the monitor loop."""
        with self._lock:
            conn = self._conns.get(rank)
        if conn is None:
            return False
        payload = json.dumps({"reason": reason}).encode("utf-8")
        frame = _HEADER.pack(len(payload) + 5, MSG_DUMP_REQ, rank) + payload
        try:
            conn.sendall(frame)
        except OSError:
            return False
        return True

    def request_profile(self, rank, reason="alert", rule=None,
                        steps=None):
        """Ask ``rank`` to capture a perf-forensics evidence window
        (xprof trace + uncapped attribution rows + memory snapshot)
        into its job dir. Same transport contract as
        :meth:`request_dump`: the guaranteed control socket, where the
        worker's framed watchdog dispatches it to the registered
        capture service. Returns False (never raises) when the rank
        has no registered connection or the send fails."""
        with self._lock:
            conn = self._conns.get(rank)
        if conn is None:
            return False
        req = {"reason": reason}
        if rule is not None:
            req["rule"] = rule
        if steps is not None:
            req["steps"] = int(steps)
        payload = json.dumps(req).encode("utf-8")
        frame = _HEADER.pack(
            len(payload) + 5, MSG_PROFILE_REQ, rank) + payload
        try:
            conn.sendall(frame)
        except OSError:
            return False
        return True

    def profile_reports(self, rank=None):
        """PROFILE_DONE report metadata: ``{rank: [meta, ...]}``, or
        the list for one rank."""
        with self._lock:
            if rank is not None:
                return list(self._profile_reports.get(rank, ()))
            return {r: list(d)
                    for r, d in self._profile_reports.items()}

    def stack_dumps(self, rank=None):
        """Collected stack-dump texts: ``{rank: [dump, ...]}``, or the
        list for one rank."""
        with self._lock:
            if rank is not None:
                return list(self._stack_dumps.get(rank, ()))
            return {r: list(d) for r, d in self._stack_dumps.items()}

    def ready_count(self):
        with self._lock:
            return len(self._ready)

    def done_count(self):
        with self._lock:
            return len(self._done)

    @property
    def exceptions(self):
        with self._lock:
            return dict(self._exceptions)

    @property
    def result_bytes(self):
        with self._lock:
            return self._result

    def close(self):
        self._closed = True
        try:
            self._srv.close()
        except OSError:
            pass
        with self._lock:
            if self._log_file is not None:
                self._log_file.close()
                self._log_file = None


class ControlPlaneClient:
    """Worker-side client for the driver control plane.

    Control messages (READY/RESULT/EXC/BYE) go over a blocking Python
    socket — they must arrive. Log traffic (LOG/USERLOG) prefers the
    native C++ transport (:mod:`sparkdl_tpu.native`): a bounded
    drop-oldest ring drained off-thread, so log volume can never stall
    the training thread (reference ``runner_base.py:65-68``). Set
    ``SPARKDL_TPU_NATIVE_LOGS=0`` to force the Python path.
    """

    def __init__(self, address, rank, secret=None):
        host, port = address.rsplit(":", 1)
        self.rank = rank
        secret = secret or os.environ.get(CONTROL_SECRET_ENV)
        if not secret:
            raise RuntimeError(
                "control-plane client needs the per-job secret "
                f"({CONTROL_SECRET_ENV} unset): refusing to open an "
                "unauthenticated channel to the driver"
            )
        self._auth = auth_frame(secret, rank)
        self._sock = socket.create_connection((host, int(port)), timeout=30)
        self._sock.settimeout(None)
        self._sock.sendall(self._auth)
        # Detect a dead driver HOST too (power-off/partition sends no
        # FIN): aggressive TCP keepalive makes the watchdog's recv fail
        # within ~1 minute instead of blocking forever.
        try:
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_KEEPIDLE, 30)
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_KEEPINTVL, 10)
            self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_KEEPCNT, 3)
        except (OSError, AttributeError):
            pass  # non-Linux: keepalive is best-effort
        self._lock = threading.Lock()
        self._closing = False
        # Perf-forensics capture hook (sparkdl_tpu.observe.capture):
        # None unless a capture service registered — the watchdog's
        # PROFILE_REQ dispatch is inert with telemetry off (the
        # zero-overhead latch extends to forensics).
        self._profile_handler = None
        self._native = None
        if os.environ.get("SPARKDL_TPU_NATIVE_LOGS", "1") != "0":
            try:
                from sparkdl_tpu.native import NativeLogSender

                # The native sender opens its own TCP connection, so it
                # carries the same auth preamble on every (re)connect.
                self._native = NativeLogSender(
                    host, int(port), rank, preamble=self._auth
                )
            except (RuntimeError, OSError):
                self._native = None

    @property
    def log_transport(self):
        """``"native"`` when log lines ride the C++ drop-oldest sender,
        ``"python"`` when they ride this client's own socket."""
        return "native" if self._native is not None else "python"

    def _send(self, mtype, payload):
        # Fault-injection hook (inert without SPARKDL_TPU_CHAOS_* env):
        # the chaos harness can delay or drop control frames to
        # simulate a flaky control plane — a dropped READY stalls the
        # gang barrier, a dropped RESULT exercises the lost-result
        # path. The native log ring is not hooked (logs are droppable
        # by design).
        from sparkdl_tpu.utils.chaos import control_frame_fate

        fate = control_frame_fate(_MSG_NAMES.get(mtype, str(mtype)))
        if fate == "drop":
            return
        if fate:
            time.sleep(fate)
        frame = _HEADER.pack(len(payload) + 5, mtype, self.rank) + payload
        with self._lock:
            try:
                self._sock.sendall(frame)
            except OSError:
                pass  # driver went away; worker will be reaped by the launcher

    def _send_json(self, mtype, obj):
        self._send(mtype, json.dumps(obj).encode("utf-8"))

    def send_ready(self):
        self._send(MSG_READY, b"")

    def send_log(self, stream, text):
        # High-volume tee'd stdout/stderr rides the native drop-oldest
        # ring (never blocks training).
        payload = json.dumps(
            {"stream": stream, "text": text[:MAX_LOG_TEXT]}
        ).encode("utf-8")
        native = self._native
        if native is not None:
            native.send(MSG_LOG, payload)
        else:
            self._send(MSG_LOG, payload)

    def send_user_log(self, text):
        # log_to_driver is low-rate and EXPLICIT — it takes the
        # guaranteed control socket, never the droppable ring
        # (reference contract: the driver prints it,
        # sparkdl/horovod/__init__.py:20-25).
        self._send_json(MSG_USERLOG, {"text": text[:MAX_LOG_TEXT]})

    def send_telemetry(self, payload_obj):
        # Observability flushes (sparkdl_tpu.observe): low-rate batched
        # snapshots, so they take the guaranteed control socket like
        # log_to_driver — never the droppable native ring (a lost
        # final flush would hide exactly the events a postmortem
        # needs). Backpressure contract unchanged: the flusher batches
        # on an interval, so volume stays bounded regardless of how
        # hot the instrumented paths run.
        self._send_json(MSG_TELEMETRY, payload_obj)

    def send_launch_spans(self, spans):
        # The worker's launch-record spans (boot, connect, hvd.init,
        # the job, compiles): a few dozen small dicts, sent once
        # before READY and once before BYE on the guaranteed socket,
        # so that the driver holds them when it counts this rank in.
        if spans:
            self._send(MSG_LAUNCH,
                       json.dumps(spans, default=str).encode("utf-8"))

    def send_heartbeat(self, payload_obj):
        # Gang-health beacon (sparkdl_tpu.observe.health): tiny JSON at
        # SPARKDL_TPU_HEARTBEAT_S rate on the guaranteed control
        # socket — the whole point is that it keeps flowing while the
        # training thread is wedged, so it must never ride the
        # droppable native ring.
        self._send_json(MSG_HEARTBEAT, payload_obj)

    def send_result(self, pickled_bytes):
        # One frame when it fits; otherwise chunk under the frame cap
        # (large returned values — e.g. model weights — are legitimate,
        # reference runner_base.py:93-95 puts no size bound on them).
        if len(pickled_bytes) <= RESULT_CHUNK:
            self._send(MSG_RESULT, pickled_bytes)
            return
        view = memoryview(pickled_bytes)
        for off in range(0, len(view), RESULT_CHUNK):
            self._send(MSG_RESULT_PART, bytes(view[off:off + RESULT_CHUNK]))
        self._send(MSG_RESULT_END, b"")

    def send_exception(self, tb_text):
        # Tracebacks can embed huge reprs; keep the tail (the raise site).
        if len(tb_text) > 4 * MAX_LOG_TEXT:
            tb_text = "...[truncated]...\n" + tb_text[-4 * MAX_LOG_TEXT:]
        self._send_json(MSG_EXC, {"traceback": tb_text})

    def send_bye(self, exit_code):
        # Drain buffered logs before announcing exit so the job log is
        # complete for clean shutdowns (drops only happen under flood).
        if self._native is not None:
            self._native.flush(timeout_ms=5000)
        self._send_json(MSG_BYE, {"exit_code": exit_code})

    def _answer_dump_request(self, payload):
        """Ship a faulthandler all-thread stack dump back to the
        driver. Runs on the WATCHDOG thread — which is exactly why it
        works: the training thread may be wedged in a collective or a
        host callback, and faulthandler reads every thread's frames
        without needing any of them to cooperate."""
        try:
            reason = json.loads(payload.decode("utf-8", "replace")).get(
                "reason", "requested")
        except ValueError:
            reason = "requested"
        from sparkdl_tpu.observe.health import dump_all_threads

        try:
            dump = dump_all_threads()
        except Exception:
            import traceback

            dump = ("<faulthandler dump failed>\n"
                    + traceback.format_exc())
        self._send_json(MSG_STACK_DUMP, {"reason": reason, "dump": dump})

    def set_profile_handler(self, handler):
        """Register the worker-side capture service's entry point for
        driver ``PROFILE_REQ`` frames (``handler(request_dict)``,
        called on the watchdog thread — it must delegate the capture
        itself to its own thread, a capture spans many steps of wall
        time and the watchdog is the driver-death detector). ``None``
        unregisters."""
        self._profile_handler = handler

    def send_profile_done(self, report_meta):
        """Answer a ``PROFILE_REQ``: JSON metadata about the finished
        (or failed) capture — report filename, trace dir, reason/rule,
        error. Rides the guaranteed control socket like
        ``STACK_DUMP``."""
        self._send_json(MSG_PROFILE_DONE, report_meta)

    def _dispatch_profile_request(self, payload):
        """Hand one PROFILE_REQ to the registered capture service;
        without one (telemetry off, or no service started) the frame
        is dropped — never an error, never any work."""
        handler = self._profile_handler
        if handler is None:
            return
        try:
            req = json.loads(payload.decode("utf-8", "replace"))
        except ValueError:
            req = {}
        if not isinstance(req, dict):
            req = {}
        try:
            handler(req)
        except Exception:
            # the watchdog must keep watching no matter what the
            # capture service does
            pass

    def start_driver_watchdog(self, grace_seconds=10.0):
        """Exit this worker when the driver disappears; answer its
        hang-diagnosis requests meanwhile.

        The only driver→worker traffic is the occasional framed
        ``DUMP_REQ`` (the hang detector asking a stalled rank for its
        stacks), so the watchdog reads frames: a complete frame is
        dispatched, EOF/reset means the driver process died (including
        SIGKILL, which the launcher's reaper can't mitigate). Orphaned
        workers would otherwise run forever, holding devices and
        distributed-runtime leases (observed: a killed driver left
        gang workers pinning the TPU claim).
        """

        def watch():
            while True:
                try:
                    head = _recv_exact(self._sock, _HEADER.size)
                    if head is not None:
                        length, mtype, _rank = _HEADER.unpack(head)
                        if 5 <= length and length - 5 <= MAX_FRAME:
                            payload = _recv_exact(self._sock, length - 5)
                            if payload is not None:
                                if mtype == MSG_DUMP_REQ:
                                    self._answer_dump_request(payload)
                                elif mtype == MSG_PROFILE_REQ:
                                    self._dispatch_profile_request(
                                        payload)
                                continue  # keep watching
                        # unframeable driver bytes: treat like a reset
                    head = None
                except OSError:
                    head = None
                if head is None:
                    break
            if self._closing:
                # Our own close() raced the recv — normal teardown of a
                # finished worker, NOT a dead driver.
                return
            import sys
            import time

            sys.stderr.write(
                "sparkdl-tpu worker: driver connection lost; exiting "
                f"in {grace_seconds:.0f}s\n"
            )
            sys.stderr.flush()
            time.sleep(grace_seconds)
            if not self._closing:
                os._exit(83)

        t = threading.Thread(
            target=watch, name="sparkdl-tpu-driver-watchdog", daemon=True
        )
        t.start()

    def close(self):
        # Mark BEFORE closing the socket: the driver watchdog must read
        # this as voluntary teardown, not driver death.
        self._closing = True
        # Detach first so racing send_log calls see None (and the
        # sender's own lock makes a send that already grabbed the
        # reference safe against the close).
        native, self._native = self._native, None
        if native is not None:
            native.close()
        try:
            self._sock.close()
        except OSError:
            pass


_worker_client = None
_worker_client_lock = threading.Lock()


def get_worker_client():
    """Return the process-wide control-plane client, or None when this
    process is not a HorovodRunner worker (then driver == worker and
    ``log_to_driver`` prints directly)."""
    global _worker_client
    with _worker_client_lock:
        if _worker_client is None:
            addr = os.environ.get(CONTROL_ADDR_ENV)
            if not addr:
                return None
            rank = int(os.environ.get(RANK_ENV, "0"))
            _worker_client = ControlPlaneClient(addr, rank)
        return _worker_client
