"""Framework-agnostic Horovod API shim, TPU-native.

Provides the ``hvd.*`` surface the reference's contract assumes (the
whole HorovodRunner design launches a Horovod gang, reference
``runner_base.py:32-37``; the north star in BASELINE.json requires
``hvd.init()/rank()/size()`` to resolve via ``jax.distributed`` and the
collective surface to ride ``jax.lax.psum`` over the ICI mesh).

Framework-specific adapters (tf.keras optimizers, torch.optim hooks)
live in the top-level drop-in ``horovod`` package so that existing
training functions using ``import horovod.tensorflow.keras as hvd`` or
``import horovod.torch as hvd`` run unmodified.

Tensors of any framework (numpy, jax, torch, tf) are accepted; results
come back in the same framework/dtype.
"""

import pickle

import numpy as np

from sparkdl_tpu.hvd import _state
from sparkdl_tpu.hvd._collectives import AVERAGE, MAX, MIN, SUM, engine
from sparkdl_tpu.utils.interop import from_numpy_like, to_numpy

# Horovod-style op constants
Average = AVERAGE
Sum = SUM
Min = MIN
Max = MAX


def init(comm=None):
    """Initialize the shim. ``comm`` is accepted for API compatibility
    with Horovod and ignored (there is no MPI in the loop)."""
    del comm
    _state.init()


def shutdown():
    _state.shutdown()


def is_initialized():
    return _state.state().initialized


def rank():
    """This process's rank: the one the launcher gave it. It is not
    ``jax.process_index()`` — a TPU runtime numbers a slice's
    processes by where their chips sit."""
    _state.require_initialized()
    return _state.state().rank


def size():
    _state.require_initialized()
    return _state.state().size


def local_rank():
    _state.require_initialized()
    return _state.state().local_rank


def local_size():
    _state.require_initialized()
    return _state.state().local_size


def cross_rank():
    """Rank of this node among nodes (horovod.cross_rank parity)."""
    _state.require_initialized()
    st = _state.state()
    return st.rank // max(st.local_size, 1)


def cross_size():
    _state.require_initialized()
    st = _state.state()
    return max(st.size // max(st.local_size, 1), 1)


def _resolve_op(average, op):
    if op is not None:
        return op
    if average is None or average is True:
        return AVERAGE
    return SUM


def _concrete_single_device_jax(x):
    """True for a concrete (non-tracer) jax.Array on one device — the
    zero-host-copy collective fast path applies."""
    import sys

    if "jax" not in sys.modules:
        return False
    import jax

    return (
        isinstance(x, jax.Array)
        and not isinstance(x, jax.core.Tracer)
        and len(x.devices()) == 1
    )


def allreduce(tensor, average=None, name=None, op=None):
    """Allreduce across all ranks. Default op is Average, matching
    Horovod's gradient-averaging semantics (required for
    DistributedOptimizer parity, BASELINE.json north star).

    Device-resident ``jax.Array`` inputs take a zero-host-copy path:
    the local shard joins the gang's global array (metadata only) and
    the reduced result stays on this process's device."""
    del name
    _state.require_initialized()
    if _concrete_single_device_jax(tensor):
        return engine().reduce_jax(tensor, _resolve_op(average, op))
    x = to_numpy(tensor)
    out = engine().reduce(np.asarray(x, order="C"), _resolve_op(average, op))
    return from_numpy_like(out, tensor)


def allreduce_async(tensor, average=None, name=None, op=None):
    """Allreduce dispatched to the engine's background thread: returns
    an :class:`~sparkdl_tpu.hvd._collectives.AsyncCollective` handle
    immediately, so the wire time overlaps whatever the caller does
    next (device compute, the next microbatch's forward). Resolve with
    ``handle.result()`` — the reduced tensor comes back in the
    caller's framework, exactly like :func:`allreduce`.

    The canonical overlap pattern — hide the gradient allreduce of
    microbatch *i* under the forward of microbatch *i+1*::

        handle = hvd.allreduce_async(grads)     # hop starts now
        next_logits = forward(next_batch)       # compute overlaps it
        grads = handle.result()                 # serialized tail only

    Ordering contract (see ``AsyncCollective``): the collective is
    enqueued with XLA before this returns, on the calling thread, so
    its cross-rank order is the caller's program order — other gang
    collectives may run between the submit and its resolution, as
    long as every rank runs the same program.

    With telemetry opted in this is the measured half of ROADMAP item
    3's overlap arc: the collective span lands on the wait thread
    (overlapped time in ``observe.perf``'s attribution), the residual
    ``result()`` blocking on the caller's thread (serialized time) —
    together, ``overlap_efficiency``.
    """
    del name
    _state.require_initialized()
    kind = _resolve_op(average, op)
    eng = engine()
    if _concrete_single_device_jax(tensor):
        # jax.Arrays are immutable — safe to dispatch from without a
        # copy
        return eng.submit_async(
            "reduce_jax", lambda: eng.reduce_jax_start(tensor, kind),
            nbytes=int(getattr(tensor, "nbytes", 0) or 0))
    # COPY the host buffer before the dispatch reads it: the canonical
    # caller mutates its grads in place while the hop is in flight
    # (that is the whole point), and a zero-copy view would let the
    # reduce read a rank-dependent mix of old and new values.
    x = np.array(to_numpy(tensor), order="C", copy=True)

    def start():
        finish = eng.reduce_start(x, kind)
        return lambda: from_numpy_like(finish(), tensor)

    return eng.submit_async("reduce", start, nbytes=int(x.nbytes))


def grouped_allreduce(tensors, average=None, name=None, op=None):
    """Fused allreduce of a tensor list: one collective per dtype
    (Horovod tensor-fusion semantics) instead of one per tensor.

    All-jax input lists stay on device: the concat/split bookkeeping
    runs as XLA ops and the collective takes the zero-host-copy path.

    One span over the whole call (``sparkdl.grouped_allreduce`` in a
    profiler trace): between a gradient program and an update program
    it names the gap the device waits out."""
    del name
    _state.require_initialized()
    from sparkdl_tpu import observe

    with observe.span("grouped_allreduce", cat="collective"):
        return _grouped_allreduce(tensors, _resolve_op(average, op))


def _grouped_allreduce(tensors, kind):
    if tensors and all(_concrete_single_device_jax(t) for t in tensors):
        import jax.numpy as jnp

        by_dtype = {}
        for i, t in enumerate(tensors):
            by_dtype.setdefault(jnp.dtype(t.dtype), []).append(i)
        out = [None] * len(tensors)
        for dtype, idxs in by_dtype.items():
            flat = (
                jnp.concatenate([tensors[i].ravel() for i in idxs])
                if len(idxs) > 1 else tensors[idxs[0]].ravel()
            )
            red = engine().reduce_jax(flat, kind)
            offset = 0
            for i in idxs:
                n = tensors[i].size
                out[i] = red[offset:offset + n].reshape(tensors[i].shape)
                offset += n
        return out
    arrays = [np.asarray(to_numpy(t), order="C") for t in tensors]
    by_dtype = {}
    for i, a in enumerate(arrays):
        by_dtype.setdefault(a.dtype, []).append(i)
    out = [None] * len(arrays)
    for dtype, idxs in by_dtype.items():
        flat = np.concatenate([arrays[i].ravel() for i in idxs]) \
            if len(idxs) > 1 else arrays[idxs[0]].ravel()
        red = engine().reduce(flat, kind)
        offset = 0
        for i in idxs:
            n = arrays[i].size
            out[i] = from_numpy_like(
                red[offset:offset + n].reshape(arrays[i].shape), tensors[i]
            )
            offset += n
    return out


def allgather(tensor, name=None):
    """Concatenate each rank's tensor along axis 0 (dim0 may differ per
    rank, per Horovod semantics)."""
    del name
    _state.require_initialized()
    x = to_numpy(tensor)
    out = engine().allgather(np.asarray(x, order="C"))
    return from_numpy_like(out, tensor)


def broadcast(tensor, root_rank, name=None):
    del name
    _state.require_initialized()
    x = to_numpy(tensor)
    out = engine().broadcast(np.asarray(x, order="C"), root_rank)
    return from_numpy_like(out, tensor)


# Payload-size limb codec for the object collectives. Sizes must ride
# a collective themselves, and every scalar carrier loses on some rig:
# float64 canonicalizes to float32 with x64 off (exact only to 2**24 —
# a ~16.7 MB pickle already decodes to the wrong byte count, silently,
# anywhere in the 2**24..2**31 window), and int64 canonicalizes to
# int32 (a >2 GiB size wraps negative). Two int32 limbs via
# divmod 2**20 survive canonicalization untouched and are exact to
# 2**51 bytes; the loud >= 2 GiB guard below still bounds the actual
# payload collective.
_SIZE_LIMB = 1 << 20


def _size_to_limbs(n):
    hi, lo = divmod(int(n), _SIZE_LIMB)
    return np.array([hi, lo], np.int32)


def _size_from_limbs(limbs):
    return int(limbs[0]) * _SIZE_LIMB + int(limbs[1])


def broadcast_object(obj, root_rank=0, name=None):
    """Pickle-based object broadcast (horovod.broadcast_object parity):
    length is broadcast first (as two int32 limbs — see the codec
    note above), then the payload as a uint8 tensor."""
    del name
    _state.require_initialized()
    if size() == 1:
        return obj
    if rank() == root_rank:
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
        limbs = _size_to_limbs(payload.shape[0])
    else:
        payload = None
        limbs = np.zeros((2,), np.int32)
    # The payload broadcast is int32-bounded, so oversize fails
    # loudly — AFTER the size exchange, so every rank raises together
    # instead of the big rank bailing pre-collective and wedging the
    # others mid-broadcast.
    n = _size_from_limbs(engine().broadcast(limbs, root_rank))
    if n >= 2**31:
        raise ValueError(
            f"broadcast_object payload is {n} bytes; the "
            "payload broadcast is int32-bounded (< 2 GiB pickled). "
            "Broadcast a reference (path/handle) instead."
        )
    if payload is None:
        payload = np.zeros((n,), np.uint8)
    payload = engine().broadcast(payload, root_rank)
    return pickle.loads(payload.tobytes())


def allgather_object(obj, name=None):
    """Pickle-based object allgather (horovod.allgather_object parity):
    returns ``[rank 0's obj, rank 1's obj, ...]``. Rides the ragged
    allgather — per-rank payload sizes may differ."""
    del name
    _state.require_initialized()
    if size() == 1:
        return [obj]
    payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8).copy()
    # Sizes ride the int32 limb codec (see note above broadcast_object:
    # float64 silently rounds to float32 precision with x64 off,
    # corrupting every unpack offset for >16.7 MB payloads; int64
    # wraps). The guard fires AFTER the size exchange so every rank
    # raises the same error together — a lone oversized rank bailing
    # pre-collective would leave the rest of the gang wedged in the
    # allgather.
    limb_rows = engine().allgather(
        _size_to_limbs(payload.shape[0])[None, :])
    counts = [_size_from_limbs(row) for row in limb_rows]
    if max(counts) >= 2**31:
        raise ValueError(
            f"allgather_object payload of {max(counts)} bytes on "
            f"rank {counts.index(max(counts))}: the payload gather is "
            "int32-bounded (< 2 GiB pickled). Gather a reference "
            "(path/handle) instead of the object."
        )
    flat = engine().allgather(payload)
    out, off = [], 0
    for n in counts:
        out.append(pickle.loads(flat[off:off + n].tobytes()))
        off += n
    return out


def barrier():
    _state.require_initialized()
    engine().barrier()


def check_synchronized(tree, name="parameters", atol=0.0):
    """Gang determinism check (SURVEY.md §5.2): verify a pytree of
    arrays is identical on every rank — the broadcast-and-compare
    guard for silent rank divergence (the bug class data-parallel
    training is most prone to). Raises RuntimeError on drift.

    ``atol=0`` (default) compares raw BYTES via an allgathered digest —
    exact at full precision (float64 included, NaN == same-bits NaN),
    and a single collective for the whole tree. ``atol > 0`` uses one
    fused min/max reduction over a flat float32 buffer (tolerances
    below float32 resolution are not detectable in that mode).
    """
    import hashlib

    import jax

    _state.require_initialized()
    if size() == 1:
        return True
    leaves = [np.asarray(to_numpy(l), order="C") for l in jax.tree.leaves(tree)]
    hint = (
        "Did you forget broadcast_parameters/broadcast_variables, or is "
        "there non-deterministic data-dependent control flow?"
    )
    if atol == 0.0:
        h = hashlib.sha256()
        for x in leaves:
            h.update(x.tobytes())
        digest = np.frombuffer(h.digest(), np.uint8).copy()
        all_digests = engine().allgather(digest[None, :])
        if not (all_digests == all_digests[0]).all():
            bad = [r for r in range(size())
                   if not (all_digests[r] == all_digests[0]).all()]
            raise RuntimeError(
                f"{name} diverged across ranks (bytewise digest mismatch "
                f"vs rank 0 on ranks {bad}). {hint}"
            )
        return True
    # numeric mode: ONE min + ONE max reduce over the fused buffer
    flat = np.concatenate(
        [x.astype(np.float32).ravel() for x in leaves]
    ) if leaves else np.zeros((0,), np.float32)
    lo = engine().reduce(flat, MIN)
    hi = engine().reduce(flat, MAX)
    spread = hi - lo
    if not np.isfinite(spread).all():
        # NaN/Inf on some rank: pmin/pmax propagate it; a NaN spread
        # must fail loudly, not compare False against atol.
        raise RuntimeError(
            f"{name} contains non-finite divergence across ranks "
            "(NaN/Inf on some rank but not others, or Inf-Inf). " + hint
        )
    drift = float(spread.max()) if flat.size else 0.0
    if drift > atol:
        # localize the worst leaf for the error message
        offset, worst = 0, (0, 0.0)
        for i, x in enumerate(leaves):
            n = x.size
            d = float(spread[offset:offset + n].max()) if n else 0.0
            if d > worst[1]:
                worst = (i, d)
            offset += n
        raise RuntimeError(
            f"{name} diverged across ranks: max spread {drift:g} "
            f"(> {atol:g}) at leaf #{worst[0]}. {hint}"
        )
    return True


def alltoall(tensor, splits=None, name=None):
    """All-to-all along axis 0. Equal splits run as ONE XLA all_to_all
    over the interconnect; ragged splits pad to the max split, exchange,
    and trim (one size exchange + one all_to_all)."""
    del name
    _state.require_initialized()
    n = size()
    x = to_numpy(tensor)
    if splits is None:
        if x.shape[0] % n:
            raise ValueError(
                f"alltoall requires dim0 ({x.shape[0]}) divisible by size ({n}) "
                "when splits is None"
            )
        splits = [x.shape[0] // n] * n
    splits = [int(s) for s in np.asarray(to_numpy(splits)).tolist()]
    if len(splits) != n or sum(splits) != x.shape[0]:
        raise ValueError(
            f"alltoall splits {splits} must have one entry per rank ({n}) "
            f"and sum to the tensor's dim0 ({x.shape[0]})"
        )
    if n == 1:
        return from_numpy_like(x.copy(), tensor)
    eng = engine()
    # The uniform-vs-ragged decision MUST be made from the globally
    # exchanged table — deciding from rank-local splits lets ranks
    # take different collective sequences and deadlock the gang.
    split_table = eng.allgather(np.asarray(splits, np.int64)[None, :])
    if (split_table == split_table.flat[0]).all():
        out = eng.alltoall_equal(np.asarray(x, order="C"))
        return from_numpy_like(out, tensor)
    # Ragged: everyone pads each destination chunk to the global max
    # split, one equal all_to_all, then trim using the exchanged table.
    max_split = int(split_table.max())
    padded = np.zeros((n * max_split,) + x.shape[1:], x.dtype)
    off = 0
    for j, s in enumerate(splits):
        padded[j * max_split : j * max_split + s] = x[off : off + s]
        off += s
    out = eng.alltoall_equal(padded)
    r = rank()
    parts = [
        out[src * max_split : src * max_split + int(split_table[src, r])]
        for src in range(n)
    ]
    return from_numpy_like(np.concatenate(parts, axis=0), tensor)


def reducescatter(tensor, op=None, name=None):
    """Reduce-scatter along axis 0 (equal chunks): one XLA
    ``psum_scatter`` — each rank receives only its reduced chunk
    (1/size the traffic of allreduce-then-slice)."""
    del name
    _state.require_initialized()
    x = to_numpy(tensor)
    out = engine().scatter_reduce(
        np.asarray(x, order="C"), _resolve_op(None, op) if op else AVERAGE
    )
    return from_numpy_like(out, tensor)


# -- capability probes (horovod API compat) ---------------------------------

def mpi_threads_supported():
    return False


def mpi_built():
    return False


def mpi_enabled():
    return False


def nccl_built():
    return False  # no GPU in the loop — XLA/ICI replaces NCCL


def gloo_built():
    return True  # CPU rigs use XLA's gloo cpu collectives


def cuda_built():
    return False


def rocm_built():
    return False


class Compression:
    """Gradient compression registry (horovod.Compression parity).

    fp16 compression halves allreduce bytes on the wire; on TPU the
    natural choice is bfloat16 (MXU-native), used when the input is a
    floating type wider than 16 bits.
    """

    class none:  # noqa: N801 — horovod spells these lowercase
        @staticmethod
        def compress(tensor):
            return tensor, None

        @staticmethod
        def decompress(tensor, ctx):
            del ctx
            return tensor

    class fp16:  # noqa: N801
        @staticmethod
        def compress(tensor):
            x = to_numpy(tensor)
            if np.issubdtype(x.dtype, np.floating) and x.dtype.itemsize > 2:
                return x.astype(np.float16), x.dtype
            return tensor, None

        @staticmethod
        def decompress(tensor, ctx):
            if ctx is None:
                return tensor
            x = to_numpy(tensor)
            return x.astype(ctx)


__all__ = [
    "init", "shutdown", "is_initialized", "rank", "size", "local_rank",
    "local_size", "cross_rank", "cross_size", "allreduce",
    "allreduce_async",
    "grouped_allreduce", "allgather", "allgather_object", "broadcast",
    "broadcast_object",
    "barrier", "alltoall", "reducescatter", "Average", "Sum", "Min",
    "Max", "Compression", "mpi_threads_supported", "mpi_built",
    "mpi_enabled", "nccl_built", "gloo_built", "cuda_built", "rocm_built",
]
