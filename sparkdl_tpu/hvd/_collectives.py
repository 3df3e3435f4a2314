"""Cross-process collectives on XLA, one rank per process.

This is the TPU-native replacement for Horovod's C++ core (ring
allreduce over MPI/NCCL/Gloo — reference contract
``runner_base.py:35``, SURVEY.md §2.2): collectives are expressed as
``jax.lax.psum``/``all_gather`` inside ``shard_map`` over a mesh with
one device per process, compiled once per (op, shape, dtype) and
executed by XLA's runtime — over ICI on a TPU pod slice, DCN across
slices, and Gloo TCP on CPU test rigs. There is no hand-written ring:
XLA picks the collective algorithm for the interconnect, which is the
whole point of building TPU-first.

All functions here take/return numpy arrays; framework adapters live in
:mod:`sparkdl_tpu.utils.interop`.
"""

import functools
import threading
import time

import numpy as np

from sparkdl_tpu import observe
from sparkdl_tpu.hvd import _state

# Reduction ops (mirror horovod.common.Op semantics)
AVERAGE = "average"
SUM = "sum"
MIN = "min"
MAX = "max"



def _observed(op_name):
    """Per-collective telemetry: op count, payload bytes, a wall-time
    histogram under ``op=<name>`` labels (the engine-level view an
    allreduce slowdown shows up in first), and a ``cat="collective"``
    span — the raw material ``observe.perf`` attributes step time
    from (a span on the step's own thread is serialized collective
    time; one on another thread is overlapped with compute). With
    telemetry off the span is only its annotation on the profiler's
    clock (``sparkdl.<op>``: a host collective BETWEEN two device
    programs shows beside the gap it makes) and the decorator never
    touches the argument."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, x, *args, **kwargs):
            if not observe.enabled():
                with observe.span(op_name, cat="collective"):
                    return fn(self, x, *args, **kwargs)
            from sparkdl_tpu.observe import health

            # Gang-health markers: the ENTRY records "last entered
            # <op>" (the line a hang postmortem shows for a rank
            # wedged inside this collective) and bumps the progress
            # counter; the EXIT bumps it again so a rank merely
            # looping fast on tiny collectives still reads as live.
            health.note_collective(op_name)
            nbytes = int(getattr(x, "nbytes", 0) or 0)
            t0 = time.perf_counter()
            with observe.span(op_name, cat="collective", op=op_name,
                              bytes=nbytes):
                out = fn(self, x, *args, **kwargs)
            dt = time.perf_counter() - t0
            health.note_collective(op_name, done=True)
            observe.inc("collective_ops_total", op=op_name)
            observe.inc("collective_bytes_total", value=nbytes,
                        op=op_name)
            observe.observe_value("collective_seconds", dt, op=op_name)
            return out

        return wrapper

    return deco


class AsyncCollective:
    """Handle for a collective dispatched to the engine's background
    thread (:meth:`_CollectiveEngine.submit_async`): the wire time runs
    concurrently with whatever the caller does next — device compute,
    the next microbatch's forward — instead of blocking the step
    thread. Resolve with :meth:`result` (the reduced tensor) or
    :meth:`wait`.

    Telemetry: the collective's ``cat="collective"`` span is recorded
    on the dispatch thread, which is exactly what ``observe.perf``
    counts as *overlapped* collective time (a span on the step thread
    is serialized time); any residual blocking inside :meth:`result`
    is recorded as a ``<op>.wait`` collective span on the calling
    thread — the serialized tail the overlap failed to hide. Together
    they are the measured ``overlap_efficiency``.

    Ordering contract: the collective is ENQUEUED with XLA on the
    submitting thread itself (``submit_async`` runs the dispatch half
    before it returns), so the cross-rank collective order is the
    caller's program order — every rank runs the same program, so
    every rank's backend sees the same sequence even when other gang
    collectives (a synchronous allreduce, a shard_map ppermute ring)
    dispatch from the step thread between a submit and its
    resolution. Only the blocking wait rides the background thread.
    """

    def __init__(self, future, op_name):
        self._future = future
        self._op = op_name

    def done(self):
        return self._future.done()

    def result(self, timeout=None):
        """The collective's result (re-raising its exception, if any).
        Blocking time is recorded as serialized collective time on the
        calling thread."""
        if self._future.done():
            return self._future.result(timeout)
        with observe.span(self._op + ".wait", cat="collective",
                          op=self._op, async_wait=True):
            return self._future.result(timeout)

    def wait(self, timeout=None):
        """Block until done (discarding the value — for callers that
        only need the barrier edge)."""
        self.result(timeout)


def _is_float_dtype(dtype):
    """numpy floats plus ml_dtypes extensions (bfloat16 etc.), which
    np.issubdtype does not recognize as np.floating."""
    if np.issubdtype(dtype, np.floating):
        return True
    try:
        import ml_dtypes

        return np.issubdtype(dtype, ml_dtypes.bfloat16) or np.issubdtype(
            dtype, ml_dtypes.float8_e4m3fn
        )
    except ImportError:  # pragma: no cover
        return False


class _CollectiveEngine:
    """Caches the mesh and compiled collective programs."""

    def __init__(self):
        self._lock = threading.Lock()
        self._mesh = None
        self._local_device = None
        self._fns = {}
        self._async_pool = None

    def _ensure_async_pool(self):
        """ONE wait thread per process: it only blocks for results
        (the dispatch already happened on the submitting thread), so
        async waits resolve in submission order and their wire time
        lands on a non-step thread in the perf attribution."""
        if self._async_pool is not None:
            return self._async_pool
        with self._lock:
            if self._async_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._async_pool = ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix="sparkdl-tpu-hvd-async",
                )
        return self._async_pool

    def submit_async(self, op_name, start, nbytes=0):
        """Run ``start`` NOW, on the calling thread — it enqueues the
        collective with XLA and returns a blocking ``finish`` thunk —
        then hand only that thunk to the background thread, where the
        wire wait lands as the overlapped ``cat="collective"`` span.

        Dispatching on the pool thread instead (the original shape)
        let the step thread's own jitted collectives race the submit
        into rank-DEPENDENT backend order: rank 0 enqueues
        [psum, ppermute] while rank 1 enqueues [ppermute, psum], each
        side's transport waits on an op the peer hasn't issued, and
        the gang deadlocks — readily reproduced on a single-core rig
        where thread scheduling is coarse. Enqueueing before
        ``submit_async`` returns pins the order to program order,
        which is identical on every rank by construction."""
        finish = start()
        pool = self._ensure_async_pool()
        if not observe.enabled():
            def finish_spanned():
                with observe.span(op_name, cat="collective"):
                    return finish()

            return AsyncCollective(pool.submit(finish_spanned), op_name)
        from sparkdl_tpu.observe import health

        def finish_observed():
            # Mirrors @_observed for the wait half: progress markers
            # for the hang detector, per-op metrics, and the timeline
            # span perf.py attributes as overlapped collective time.
            health.note_collective(op_name)
            t0 = time.perf_counter()
            with observe.span(op_name, cat="collective", op=op_name,
                              bytes=int(nbytes)):
                out = finish()
            dt = time.perf_counter() - t0
            health.note_collective(op_name, done=True)
            observe.inc("collective_ops_total", op=op_name)
            observe.inc("collective_bytes_total", value=int(nbytes),
                        op=op_name)
            observe.observe_value("collective_seconds", dt, op=op_name)
            return out

        return AsyncCollective(pool.submit(finish_observed), op_name)

    def _ensure_mesh(self):
        import jax
        from jax.sharding import Mesh

        if self._mesh is not None:
            return
        with self._lock:
            if self._mesh is not None:
                return
            # One participating device per process: rank r contributes
            # the first addressable device of ITS process — which the
            # runtime need not number r (_state._exchange_ranks).
            # Remaining local devices stay free for the user's own
            # data-plane meshes.
            by_proc = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, d)
            rank_of = _state.state().rank_of_process
            devs = [None] * len(by_proc)
            for p in sorted(by_proc):
                devs[rank_of[p] if rank_of else p] = by_proc[p]
            self._mesh = Mesh(np.array(devs), ("hvd",))
            mine = jax.process_index()
            self._local_device = by_proc[mine]

    def _compiled(self, kind, shape, dtype):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (kind, shape, str(dtype))
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        self._ensure_mesh()
        mesh = self._mesh
        # Reduction kinds drop the stacking axis INSIDE the compiled
        # program (block (1, *S) in → S out): callers get the final
        # shape straight from the shard with no eager slice op
        # (measured ~5 ms/call on 16 MB for an eager [0]).
        if kind == "sum":
            body = lambda x: jax.lax.psum(x[0], "hvd")
        elif kind == "avg":
            # Average INSIDE the compiled program: host-side division
            # would allocate + traverse the full tensor again per call
            # (measured ~2x end-to-end allreduce time at 64 MB).
            body = lambda x: (
                jax.lax.psum(x[0], "hvd") / jax.lax.axis_size("hvd")
            )
        elif kind == "min":
            body = lambda x: jax.lax.pmin(x[0], "hvd")
        elif kind == "max":
            body = lambda x: jax.lax.pmax(x[0], "hvd")
        elif kind == "gather":
            # tiled all_gather along leading axis
            body = lambda x: jax.lax.all_gather(x, "hvd", axis=0, tiled=True)
        elif kind in ("scatter_sum", "scatter_avg"):
            # True reduce-scatter: ONE psum_scatter moves 1/n the bytes
            # of the old allreduce-then-slice (each rank receives only
            # its reduced chunk — XLA lowers to reduce-scatter on ICI).
            def body(x):
                out = jax.lax.psum_scatter(
                    x[0], "hvd", scatter_dimension=0, tiled=True
                )
                if kind == "scatter_avg":
                    out = out / jax.lax.axis_size("hvd")
                return out
        elif kind[0] == "bcast":
            # True broadcast: binary-tree ppermute — the set of ranks
            # holding root's block doubles each round (ppermute pairs
            # must have unique sources, so one-to-many needs log2(n)
            # rounds). n-1 block-sends total vs the old zeros+psum
            # (a full allreduce: ~2(n-1)/n × the bytes on every link
            # plus the reduction).
            root = kind[1]
            n = self._mesh.devices.size
            rounds = []
            span = 1
            while span < n:
                perm = [
                    ((root + p) % n, (root + p + span) % n)
                    for p in range(min(span, n - span))
                ]
                rounds.append((span, min(2 * span, n), perm))
                span *= 2

            def body(x):
                import jax.numpy as jnp

                blk = x[0]
                p_rel = (jax.lax.axis_index("hvd") - root) % n
                cur = blk
                for lo, hi, perm in rounds:
                    sent = jax.lax.ppermute(cur, "hvd", perm)
                    is_recv = (p_rel >= lo) & (p_rel < hi)
                    cur = jnp.where(is_recv, sent, cur)
                return cur
        elif kind == "alltoall":
            # shard_map block (1, n*chunk, ...): exchange chunk j with
            # rank j in one collective (XLA all-to-all over ICI).
            def body(x):
                blk = x[0]  # (n*chunk, ...)
                n = jax.lax.axis_size("hvd")
                parts = blk.reshape((n, blk.shape[0] // n) + blk.shape[1:])
                out = jax.lax.all_to_all(
                    parts, "hvd", split_axis=0, concat_axis=0, tiled=False
                )
                return out.reshape(blk.shape)[None]
        else:
            raise ValueError(kind)
        # alltoall/reduce-scatter outputs stay partitioned (each rank
        # receives its own slices); reductions/gathers/broadcasts
        # replicate. The replication checker can't infer
        # all_gather/all_to_all/ppermute/psum_scatter semantics —
        # disable for those.
        partitioned = kind in ("alltoall", "scatter_sum", "scatter_avg")
        out_spec = P("hvd") if partitioned else P()
        check_vma = not (
            partitioned or kind == "gather" or kind[0] == "bcast")
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                fn = jax.jit(
                    jax.shard_map(
                        body, mesh=mesh, in_specs=P("hvd"),
                        out_specs=out_spec, check_vma=check_vma,
                    ),
                    out_shardings=NamedSharding(mesh, out_spec),
                )
                self._fns[key] = fn
        return fn

    def _to_global(self, local_np):
        """Stack rank-local arrays along a new leading 'hvd' axis as one
        global jax.Array (shape (size, *local.shape), sharded on hvd)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._ensure_mesh()
        size = _state.state().size
        local = jax.device_put(local_np[None], self._local_device)
        return jax.make_array_from_single_device_arrays(
            (size,) + local_np.shape,
            NamedSharding(self._mesh, P("hvd")),
            [local],
        )

    def _local_out(self, global_arr):
        # out_specs=P() → replicated; read this process's shard.
        shard = global_arr.addressable_shards[0].data
        return np.asarray(shard)

    # -- public ops ---------------------------------------------------------

    def reduce_start(self, x_np, op):
        """Dispatch half of :meth:`reduce`: resolve the compiled
        program and ENQUEUE the collective on the calling thread —
        pinning its cross-rank order to program order — and return a
        ``finish()`` thunk that blocks for the wire and materializes
        the reduced numpy array (:meth:`submit_async` runs that half
        on the wait thread; :meth:`reduce` runs it inline)."""
        st = _state.state()
        if st.size == 1:
            out = (x_np.copy() if op != AVERAGE
                   else x_np.astype(x_np.dtype))
            return lambda: out
        # Float averages divide in-graph ("avg" kind); integer/bool
        # averages keep the host path (horovod's truncate-back-to-int
        # semantics need the float64 detour).
        in_graph_avg = op == AVERAGE and _is_float_dtype(x_np.dtype)
        kind = (
            "avg" if in_graph_avg
            else "sum" if op in (SUM, AVERAGE) else op
        )
        src_dtype = x_np.dtype
        squeeze_bool = src_dtype == np.bool_
        if squeeze_bool:
            x_np = x_np.astype(np.uint8)
        fn = self._compiled(kind, x_np.shape, x_np.dtype)
        pending = fn(self._to_global(x_np))

        def finish():
            out = self._local_out(pending)
            if op == AVERAGE and not in_graph_avg:
                if np.issubdtype(out.dtype, np.integer):
                    out = out.astype(np.float64)
                out = out / st.size
                out = out.astype(src_dtype) if not squeeze_bool else out
            elif in_graph_avg:
                # XLA may canonicalize the compute dtype (f64 -> f32
                # with x64 disabled); the caller's dtype is the
                # contract. copy is a no-op when the dtype already
                # matches.
                out = out.astype(src_dtype, copy=False)
            if squeeze_bool:
                out = out.astype(np.bool_)
            return out

        return finish

    @_observed("reduce")
    def reduce(self, x_np, op):
        return self.reduce_start(x_np, op)()

    def reduce_jax_start(self, x, op):
        """Dispatch half of :meth:`reduce_jax` (same split contract as
        :meth:`reduce_start`): the collective is enqueued HERE, the
        returned ``finish()`` only blocks for the device result."""
        import jax

        import jax.numpy as jnp

        st = _state.state()
        if st.size == 1:
            return lambda: x
        self._ensure_mesh()
        in_graph_avg = op == AVERAGE and _is_float_dtype(x.dtype)
        if op == AVERAGE and not in_graph_avg:
            # integer/bool average needs the host detour for horovod's
            # truncation semantics; rare for device-resident tensors.
            # Re-wrap as a jax.Array: reduce_jax's contract is
            # jax.Array in, jax.Array out.
            host_finish = self.reduce_start(np.asarray(x), op)
            return lambda: jax.device_put(
                host_finish(), self._local_device
            )
        kind = "avg" if in_graph_avg else (
            "sum" if op in (SUM, AVERAGE) else op
        )
        squeeze_bool = x.dtype == jnp.bool_
        if squeeze_bool:
            # Match the host path's bool semantics: reduce as uint8 and
            # restore (XLA would widen a bool psum to int32 counts).
            x = x.astype(jnp.uint8)
        fn = self._compiled(kind, tuple(x.shape), x.dtype)
        from jax.sharding import NamedSharding, PartitionSpec as P

        local = jax.device_put(x[None], self._local_device)
        global_arr = jax.make_array_from_single_device_arrays(
            (st.size,) + tuple(x.shape),
            NamedSharding(self._mesh, P("hvd")),
            [local],
        )
        out = fn(global_arr).addressable_shards[0].data

        def finish():
            got = out
            if hasattr(got, "block_until_ready"):
                got = got.block_until_ready()
            if squeeze_bool:
                got = got.astype(jnp.bool_)
            return got

        return finish

    @_observed("reduce_jax")
    def reduce_jax(self, x, op):
        """Allreduce a DEVICE-RESIDENT ``jax.Array`` without any host
        crossing: assembling the global array from the local shard is
        metadata-only, the collective is the same compiled shard_map
        psum, and the returned array stays on this process's device.
        This is the fast path for framework grads that already live on
        the chip (keras-3-jax custom loops, dlpack'd torch tensors)."""
        return self.reduce_jax_start(x, op)()

    @_observed("allgather")
    def allgather(self, x_np):
        """Horovod allgather: concatenate along axis 0; ranks may have
        different dim0 (horovod semantics). Implemented as size-exchange
        + pad + tiled all_gather + trim."""
        st = _state.state()
        if st.size == 1:
            return x_np.copy()
        if x_np.ndim == 0:
            x_np = x_np[None]
        sizes = np.zeros((st.size,), np.int32)
        sizes[st.rank] = x_np.shape[0]
        sizes = self.reduce(sizes, SUM)
        max_d0 = int(sizes.max())
        pad = max_d0 - x_np.shape[0]
        padded = (
            np.concatenate(
                [x_np, np.zeros((pad,) + x_np.shape[1:], x_np.dtype)], axis=0
            )
            if pad
            else x_np
        )
        fn = self._compiled("gather", padded.shape, padded.dtype)
        # shard_map in_specs=P('hvd') gives each rank its (1, max_d0, ...)
        # block; all_gather(tiled, axis=0) over the leading axis yields
        # (size, max_d0, ...) replicated.
        gathered = self._local_out(fn(self._to_global(padded)))
        parts = [gathered[r, : int(sizes[r])] for r in range(st.size)]
        return np.concatenate(parts, axis=0)

    @_observed("alltoall")
    def alltoall_equal(self, x_np):
        """Equal-split all-to-all: local (n*chunk, ...) in, local
        (n*chunk, ...) out where slot j holds rank j's chunk for us —
        ONE XLA all_to_all over the interconnect (not gather+slice)."""
        st = _state.state()
        if st.size == 1:
            return x_np.copy()
        fn = self._compiled("alltoall", x_np.shape, x_np.dtype)
        out = fn(self._to_global(x_np))
        return np.asarray(out.addressable_shards[0].data)[0]

    @_observed("scatter_reduce")
    def scatter_reduce(self, x_np, op):
        """Reduce-scatter along axis 0 (dim0 divisible by size): each
        rank receives its own reduced ``dim0/size`` chunk via ONE
        ``psum_scatter`` — 1/size the interconnect bytes of
        allreduce-then-slice. ``op`` ∈ {SUM, AVERAGE} (floats reduce
        in-graph; integer averages truncate on host like :meth:`reduce`)."""
        st = _state.state()
        n = st.size
        if x_np.shape[0] % n:
            raise ValueError(
                f"scatter_reduce requires dim0 ({x_np.shape[0]}) "
                f"divisible by size ({n})"
            )
        chunk = x_np.shape[0] // n
        if n == 1:
            return x_np.copy()
        if op not in (SUM, AVERAGE):
            # min/max have no scatter form in XLA; full reduce + slice
            full = self.reduce(x_np, op)
            return full[st.rank * chunk:(st.rank + 1) * chunk]
        orig_dtype = x_np.dtype
        squeeze_bool = orig_dtype == np.bool_
        if squeeze_bool:
            # same semantics as reduce(): XLA would widen a bool psum
            x_np = x_np.astype(np.uint8)
        host_avg = op == AVERAGE and not _is_float_dtype(x_np.dtype)
        kind = "scatter_avg" if op == AVERAGE and not host_avg \
            else "scatter_sum"
        fn = self._compiled(kind, x_np.shape, x_np.dtype)
        out = np.asarray(
            fn(self._to_global(x_np)).addressable_shards[0].data
        )
        assert out.shape[0] == chunk
        if host_avg:
            out = out.astype(np.float64) / n
        if squeeze_bool:
            out = out.astype(np.bool_)
        else:
            # XLA may canonicalize (f64->f32 without x64); the
            # caller's dtype is the contract, as in reduce().
            out = out.astype(orig_dtype, copy=False)
        return out

    @_observed("broadcast")
    def broadcast(self, x_np, root_rank):
        st = _state.state()
        if st.size == 1:
            return x_np.copy()
        fn = self._compiled(
            ("bcast", int(root_rank)), x_np.shape, x_np.dtype
        )
        return self._local_out(fn(self._to_global(x_np)))

    def barrier(self):
        self.reduce(np.zeros((1,), np.float32), SUM)

    def reset(self):
        # Drain the dispatch pool BEFORE clearing engine state: an
        # in-flight async collective would otherwise rebuild the old
        # gang's mesh/compiled fns after the clear, leaving stale
        # state for the next init.
        with self._lock:
            pool, self._async_pool = self._async_pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        with self._lock:
            self._mesh = None
            self._local_device = None
            self._fns = {}


_engine = _CollectiveEngine()


def engine():
    return _engine
