"""Worker-process bootstrap for HorovodRunner gangs.

Executed as ``python -m sparkdl_tpu.horovod._worker`` by the launcher.
Reconstructs the distributed contract the reference documents but never
implements (reference ``runner_base.py:54-61``): join the gang
rendezvous, bind the device, deserialize the user ``main`` (cloudpickle,
reference ``runner_base.py:82-83``), run it, and ship rank 0's return
value back to the driver (reference ``runner_base.py:93-95``).

Log routing: this process's stdout/stderr are tee'd — every line goes to
a per-rank file in the job dir AND over the control plane to the driver,
which merges all ranks into the job log (reference ``runner_base.py:
62-72``).
"""

import contextlib
import io
import os
import sys
import time
import traceback

# where a worker's boot span starts when the kernel's record of the
# process start cannot be read (observe.launch.process_start_time)
_IMPORTED = time.time()


class _NullFile:
    """Stand-in local log for environments without a job dir (Spark
    barrier tasks tee straight to the control plane)."""

    def write(self, s):
        return len(s)

    def flush(self):
        pass

    def close(self):
        pass


class _TeeStream(io.TextIOBase):
    """Line-buffering tee: forwards complete lines to the control plane
    and writes through to a local per-rank log file."""

    def __init__(self, stream_name, local_file, client):
        self.stream_name = stream_name
        self.local_file = local_file
        self.client = client
        self._buf = ""

    def write(self, s):
        if not isinstance(s, str):
            s = s.decode("utf-8", "replace")
        self.local_file.write(s)
        self._buf += s
        while "\n" in self._buf:
            line, self._buf = self._buf.split("\n", 1)
            if self.client is not None:
                self.client.send_log(self.stream_name, line)
        return len(s)

    def flush(self):
        self.local_file.flush()
        if self._buf:
            if self.client is not None:
                self.client.send_log(self.stream_name, self._buf)
            self._buf = ""

    @property
    def closed(self):
        return False

    def writable(self):
        return True


def _set_parent_death_signal():
    """Linux second line of defense: SIGTERM this worker if its parent
    (the launcher) dies before the watchdog notices."""
    try:
        import ctypes
        import signal

        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        PR_SET_PDEATHSIG = 1
        libc.prctl(PR_SET_PDEATHSIG, signal.SIGTERM)
    except OSError:
        pass


@contextlib.contextmanager
def worker_io(rank, local_log_path=None):
    """The worker observability bootstrap, shared by the local gang
    worker and Spark barrier tasks: control-plane client + driver
    watchdog, parent-death signal, stdout/stderr tee to the driver (so
    ``driver_log_verbosity`` works in EVERY backend, reference
    ``runner_base.py:62-72``), EXC frames on failure, BYE on exit.

    Yields the control-plane client (None outside a job). Exceptions
    propagate to the caller after their traceback has been teed and
    shipped as an EXC frame."""
    from sparkdl_tpu import observe
    from sparkdl_tpu.horovod.control_plane import get_worker_client

    # Launch record (always on, no thread): the control-plane connect
    # and, from here on, every trace, lowering and compile or cache
    # load JAX reports are lifecycle spans; they ride to the driver in
    # LAUNCH frames, one before READY (main) and one before BYE (below).
    observe.watch_compiles()
    with observe.span("worker.connect", cat="launch"):
        client = get_worker_client()
    if client is not None:
        # Fail-fast failure detection in BOTH directions: the launcher
        # reaps dead workers; this reaps workers whose DRIVER died
        # (even via SIGKILL) so orphans never keep their chips —
        # and the same watchdog thread answers the driver's
        # hang-diagnosis DUMP_REQ frames with faulthandler stacks.
        client.start_driver_watchdog()
    heartbeat = None
    flightrec = None
    capture = None
    if client is not None and observe.enabled():
        # Telemetry transport: periodic batched flushes of this
        # worker's metric snapshot + timeline events over the control
        # plane (TELEMETRY frames), merged gang-wide on the driver.
        observe.set_sink(client.send_telemetry)
        observe.start_flusher()
        # Flight recorder: mirror every timeline event into an
        # mmap-backed ring in the job dir so the tail survives a
        # SIGKILL between flushes (the driver recovers it into the
        # merged run dir). Job-dir-less backends (Spark barrier
        # tasks) skip it — there is no shared dir to recover from.
        job_dir = os.environ.get("SPARKDL_TPU_JOB_DIR")
        if job_dir:
            from sparkdl_tpu.observe.flightrec import (
                FlightRecorder,
                ring_path,
            )

            try:
                flightrec = FlightRecorder(ring_path(job_dir, rank))
                observe.set_flight_recorder(flightrec)
            except OSError:
                flightrec = None  # unwritable dir: telemetry still works
        # Gang health: liveness beacons on the guaranteed control
        # socket — they keep flowing while the training thread is
        # wedged, which is what lets the driver tell a hang from a
        # long step (sparkdl_tpu.observe.health).
        from sparkdl_tpu.observe.health import HeartbeatSender

        heartbeat = HeartbeatSender(client, rank)
        heartbeat.start()
        # Memory accounting: the low-rate sampler keeps the beacon's
        # mem field fresh (category gauges, host RSS, unattributed
        # residual) — behind the same latch, so no env means no
        # thread (sparkdl_tpu.observe.mem).
        from sparkdl_tpu.observe import mem

        mem.maybe_start_sampler()
        # Perf forensics: answer the driver's PROFILE_REQ frames (and
        # the fixed-step self-trigger) with bounded capture windows —
        # xprof trace + uncapped attribution rows into the job dir.
        # Installed AFTER the flight recorder so its timeline tap
        # chains over the recorder's mirror; None without a job dir
        # (sparkdl_tpu.observe.capture).
        from sparkdl_tpu.observe.capture import (
            maybe_start_capture_service,
        )

        capture = maybe_start_capture_service(client, rank)
        observe.instant("worker.start", cat="worker", rank=rank)
    _set_parent_death_signal()
    local_log = (
        open(local_log_path, "a", buffering=1) if local_log_path
        else _NullFile()
    )
    orig_stdout, orig_stderr = sys.stdout, sys.stderr
    sys.stdout = _TeeStream("stdout", local_log, client)
    sys.stderr = _TeeStream("stderr", local_log, client)
    exit_code = 0
    try:
        yield client
    except BaseException as e:
        exit_code = 1
        tb = traceback.format_exc()
        sys.stderr.write(tb + "\n")
        if client is not None:
            client.send_exception(tb)
        # Mark as already-recorded so outer handlers don't duplicate
        # the traceback into the same log.
        e._sparkdl_recorded = True
        raise
    finally:
        sys.stdout.flush()
        sys.stderr.flush()
        # Interpreter shutdown flushes sys.stdout/err; the tees' backing
        # file is about to close, so restore the originals first.
        sys.stdout, sys.stderr = orig_stdout, orig_stderr
        if client is not None:
            if observe.enabled():
                if capture is not None:
                    # BEFORE the flight recorder teardown below: the
                    # capture tap chains over the recorder's mirror
                    # and must restore it cleanly.
                    capture.stop()
                if heartbeat is not None:
                    heartbeat.stop()
                from sparkdl_tpu.observe import mem

                mem.stop_sampler()
                # Final flush BEFORE the BYE: the driver treats BYE as
                # this rank's last word, and the tail of the timeline
                # (checkpoint saves, the last step spans) must not
                # die with the process.
                observe.instant("worker.exit", cat="worker", rank=rank,
                                exit_code=exit_code)
                observe.stop_flusher()
                observe.flush()
                observe.set_sink(None)
                if flightrec is not None:
                    observe.set_flight_recorder(None)
                    flightrec.close()
            client.send_launch_spans(observe.launch_record().drain())
            client.send_bye(exit_code)
            client.close()
        local_log.close()


def main():
    from sparkdl_tpu.hvd import _state
    from sparkdl_tpu.utils import locksan

    # Opt-in lock-order sanitizer: must run before any worker-side
    # lock is constructed (control-plane client, observe sinks) so the
    # observed acquisition-order graph covers them all.
    locksan.maybe_install()

    rank = int(os.environ["SPARKDL_TPU_RANK"])
    job_dir = os.environ["SPARKDL_TPU_JOB_DIR"]
    payload_path = os.environ["SPARKDL_TPU_PAYLOAD"]

    # Remote-exec'd workers (ssh transport): the boot stream arrives
    # over stdin ("-") — control-plane secret first (argv/env on the
    # ssh command line are world-readable in /proc; stdin is not),
    # then the payload — and the driver's job dir doesn't exist on
    # this machine, so make a local copy for the per-rank log. Only
    # the secret LINE is read eagerly: the payload body can be GBs
    # over a slow link, and draining it here would burn the gang
    # start timeout that local workers (who open a file at step 5)
    # never pay. The body waits in the pipe until after READY.
    payload_from_stdin = payload_path == "-"
    if payload_from_stdin and (
            os.environ.get("SPARKDL_TPU_CONTROL_SECRET") == "stdin"):
        secret = sys.stdin.buffer.readline().rstrip(b"\n")
        os.environ["SPARKDL_TPU_CONTROL_SECRET"] = secret.decode()
    os.makedirs(job_dir, exist_ok=True)

    # 1. Platform selection must happen before any JAX backend init.
    _state.ensure_jax_platform()

    # 1b. Warm-start compilation: point JAX's persistent compile cache
    # at the gang-wide dir BEFORE backend init, so this worker — a
    # fresh attempt's relaunch included — reuses every XLA artifact a
    # previous incarnation paid for. No-op unless the launcher shipped
    # JAX_COMPILATION_CACHE_DIR (see sparkdl_tpu/parallel/compile).
    from sparkdl_tpu.parallel.compile import enable_persistent_cache

    enable_persistent_cache()

    from sparkdl_tpu import observe
    from sparkdl_tpu.observe.launch import process_start_time

    # everything up to here is boot: the interpreter, this package's
    # imports, JAX's (ensure_jax_platform)
    boot = process_start_time() or _IMPORTED
    observe.complete("worker.boot", boot, time.time() - boot, cat="launch")

    exit_code = 0
    try:
        # 2. Control plane + log tee (before anything can print).
        with worker_io(
            rank, os.path.join(job_dir, f"rank-{rank}.log")
        ) as client:
            # 3. Gang rendezvous: jax.distributed.initialize against
            # the launcher's coordinator (replaces MPI rendezvous,
            # BASELINE.json). The chaos hook sits in front of it so a
            # fault-injection schedule can stall or kill this rank
            # before it joins — inert without SPARKDL_TPU_CHAOS_* env.
            from sparkdl_tpu.utils.chaos import on_worker_boot

            on_worker_boot(rank)

            import sparkdl_tpu.hvd as hvd

            hvd.init()
            # one worker.backend a rank a launch: a gang's ranks reached
            # their chips inside hvd.init, a single worker does here, so
            # that a worker that reports READY has its chip
            if not any(s["name"] == "worker.backend"
                       for s in observe.launch_record().report()):
                _state.reach_backend()

            # 4. Tell the driver this worker is up (gang barrier on the
            # driver side — fail-fast if any worker never arrives,
            # reference runner_base.py:54-58).
            if client is not None:
                # the spans so far first: the driver holds this rank's
                # boot, connect, hvd.init and reach of its chip when it
                # counts it ready
                client.send_launch_spans(observe.launch_record().drain())
                client.send_ready()
            observe.instant("worker.ready", cat="worker", rank=rank)
            if observe.enabled():
                # Build-info correlation (ISSUE 14 satellite): stamp
                # build_info{git_sha,jax_version,device_kind} AFTER
                # backend init so the device kind is real — every
                # telemetry flush from here carries it, so the gang
                # /metrics scrape and the run-dir metrics.prom join
                # on sha without guessing.
                from sparkdl_tpu.observe.metrics import ensure_build_info

                ensure_build_info(observe.metrics())

            # 5. Deserialize and run the user main (under a per-rank
            # profiler trace when SPARKDL_TPU_PROFILE is set).
            import cloudpickle

            from sparkdl_tpu.utils.profiler import maybe_trace_worker

            if payload_from_stdin:
                user_main, kwargs = cloudpickle.loads(
                    sys.stdin.buffer.read())
            else:
                with open(payload_path, "rb") as f:
                    user_main, kwargs = cloudpickle.load(f)
            with maybe_trace_worker(rank), \
                    observe.span("worker.job", cat="launch"):
                result = user_main(**kwargs)

            # 6. Rank 0's return value goes back to the driver.
            if hvd.rank() == 0 and client is not None:
                client.send_result(cloudpickle.dumps(result))
    except BaseException as e:
        exit_code = 1
        if not getattr(e, "_sparkdl_recorded", False):
            # Bootstrap failure BEFORE the tee existed (control plane
            # unreachable, unwritable job dir): stderr is still the
            # launcher's O_APPEND boot log — print there or the
            # launcher reports an opaque 'exited 1' with an empty log.
            traceback.print_exc()
    sys.exit(exit_code)


if __name__ == "__main__":
    main()
