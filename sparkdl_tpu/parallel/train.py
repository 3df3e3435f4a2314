"""pjit train-step factory: the path a JAX ``main`` uses under
HorovodRunner (SURVEY.md §7 step 7 — mesh ('data','model') so the
Llama-LoRA north-star config launches through the same runner).

The step is GSPMD-sharded end to end: params carry NamedShardings from
:func:`sparkdl_tpu.parallel.sharding.param_sharding`, the batch is
sharded on ``data`` (and optionally ``seq``), gradients reduce over the
data axes automatically because XLA derives the collectives from the
shardings — no explicit psum, no hand-scheduled overlap.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np


def make_train_step(loss_fn, optimizer, *, grad_accum=1, remat=False,
                    param_mask=None):
    """Build ``step(params, opt_state, batch, *extra) -> (params,
    opt_state, metrics)``.

    :param loss_fn: ``f(params, batch, *extra) -> scalar loss``.
    :param optimizer: an optax GradientTransformation.
    :param grad_accum: microbatch count; the batch's leading axis is
        split and gradients averaged via ``lax.scan`` (HBM-friendly:
        activations live one microbatch at a time).
    :param remat: wrap loss_fn in ``jax.checkpoint`` — trade FLOPs for
        HBM on long sequences.
    :param param_mask: optional pytree of bools; False leaves are
        frozen (LoRA-style partial training). Frozen leaves are
        ``stop_gradient``-ed going INTO the loss so XLA never emits
        their dW matmuls (the x^T·dy pass — ~1/3 of backward FLOPs
        when most of the model is frozen); activation gradients still
        flow through them. BOTH the resulting (zero) gradients and
        final updates are masked — masking grads alone would let
        decoupled weight decay (adamw) silently erode frozen weights.
    """
    if param_mask is not None:
        inner_loss = loss_fn

        def loss_fn(params, *a):  # noqa: F811 — deliberate wrap
            params = jax.tree.map(
                lambda p, m: p if m else jax.lax.stop_gradient(p),
                params, param_mask,
            )
            return inner_loss(params, *a)

    f = jax.checkpoint(loss_fn) if remat else loss_fn
    grad_fn = jax.value_and_grad(f)

    def apply_mask(tree):
        if param_mask is None:
            return tree
        return jax.tree.map(
            lambda g, m: g if m else jnp.zeros_like(g), tree, param_mask
        )

    @jax.named_scope("sparkdl.optimizer")
    def update(params, opt_state, grads):
        grads = apply_mask(grads)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        updates = apply_mask(updates)
        return jax.tree.map(lambda p, u: p + u, params, updates), opt_state

    def single(params, opt_state, batch, *extra):
        loss, grads = grad_fn(params, batch, *extra)
        params, opt_state = update(params, opt_state, grads)
        return params, opt_state, {"loss": loss}

    if grad_accum == 1:
        return single

    def accumulated(params, opt_state, batch, *extra):
        micro = jax.tree.map(
            lambda x: x.reshape((grad_accum, x.shape[0] // grad_accum)
                                + x.shape[1:]),
            batch,
        )

        def acc_step(carry, mb):
            g_acc, l_acc = carry
            loss, grads = grad_fn(params, mb, *extra)
            g_acc = jax.tree.map(jnp.add, g_acc, grads)
            return (g_acc, l_acc + loss), None

        zeros = jax.tree.map(jnp.zeros_like, params)
        (g_sum, l_sum), _ = jax.lax.scan(acc_step, (zeros, 0.0), micro)
        grads = jax.tree.map(lambda g: g / grad_accum, g_sum)
        params, opt_state = update(params, opt_state, grads)
        return params, opt_state, {"loss": l_sum / grad_accum}

    return accumulated


def instrument_step(step_fn, name="train_step"):
    """Wrap a (possibly jitted) train step with gang telemetry
    (:mod:`sparkdl_tpu.observe`): a timeline span per call, a
    wall-time histogram split ``phase="compile"`` (first call — under
    jit that call pays trace + XLA compile) vs ``phase="execute"``,
    a call counter, and a running ``<name>_per_second`` gauge over the
    execute calls. Telemetry off (the default): the call runs under
    its ``observe.span`` alone, which is then only the annotation
    ``sparkdl.<name>`` on the profiler's clock (a no-op inside JAX
    when no profiler session is open).

    Timing is dispatch wall-time, deliberately: blocking on the result
    every step would serialize the async dispatch pipeline the whole
    runner exists to keep full. Steady-state steps/sec is still
    accurate — a saturated pipeline's dispatch rate IS its device
    rate — and the compile-vs-execute split isolates the one honest
    outlier (the first call blocks on XLA anyway).

    When an executable cost was registered for ``name``
    (:func:`sparkdl_tpu.observe.perf.register_step_cost` — the
    compile cache and :func:`lower_train_step` both do), each execute
    call also updates the achieved-FLOPs/s, achieved-bytes/s, MFU and
    memory-bandwidth-utilization gauges against the per-device-kind
    peak table.
    """
    from sparkdl_tpu import observe

    state = {"calls": 0, "first_exec_t0": None}

    @functools.wraps(step_fn)
    def stepped(*args, **kwargs):
        if not observe.enabled():
            with observe.span(name, cat="train"):
                return step_fn(*args, **kwargs)
        from sparkdl_tpu.observe import health

        # Step ENTRY is the gang-health progress marker: a rank that
        # stops entering steps stops moving this counter, which is
        # what the driver's HangDetector declares a stall on. Entry
        # (not exit) so a long first-step compile pins the counter
        # for at most one compile.
        health.note_step(state["calls"])
        phase = "compile" if state["calls"] == 0 else "execute"
        t0 = time.perf_counter()
        from sparkdl_tpu.observe import mem

        # OOM forensics (ISSUE 18): an allocation failure inside the
        # step writes oom_report.json (category table, sample tail,
        # hints) before the exception unwinds the worker.
        with mem.oom_guard(phase="step"), \
                observe.span(name, cat="train", step=state["calls"],
                             ident=f"{name}-{state['calls']}",
                             phase=phase):
            out = step_fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        state["calls"] += 1
        observe.observe_value(f"{name}_seconds", dt, phase=phase)
        observe.inc(f"{name}_total", phase=phase)
        if phase == "execute":
            from sparkdl_tpu.observe import perf

            perf.note_step(name, dt)
            if state["first_exec_t0"] is None:
                state["first_exec_t0"] = t0
            elapsed = time.perf_counter() - state["first_exec_t0"]
            if elapsed > 0:
                observe.set_gauge(
                    f"{name}_per_second",
                    (state["calls"] - 1) / elapsed,
                )
        return out

    return stepped


def lower_train_step(step, *example_args, mesh=None,
                     cost_name="train_step", donate_argnums=None):
    """Version-stable lowered-module access for a (jitted or plain)
    train step: returns the ``jax.stages.Lowered`` for
    ``step(*example_args)``, entering ``mesh`` around lowering when
    given (GSPMD programs lower against the ambient mesh).

    ``donate_argnums`` re-jits the step with the given arguments
    donated before lowering (an outer ``jax.jit`` restores donation
    even on an already-jitted undonated step) — the manual seam for
    applying a ``donate-step-buffers`` fix's inferred argnums
    (:mod:`sparkdl_tpu.analysis.fixes`) by hand, so the repaired
    step's buffers alias in the same artifact the compile cache
    serializes.

    This is the artifact the static-analysis passes consume
    (:mod:`sparkdl_tpu.analysis`): lower once on the driver, then
    lint and ``.compile()`` the same object — nothing is traced
    twice. (Compilation is separate: lint the *Compiled* via
    ``analysis.lint_compiled`` / ``register_preflight`` when you will
    compile anyway, so the expensive compile runs once too.)

    With telemetry opted in, the lowering's analytic FLOPs/bytes are
    registered under ``cost_name`` so :func:`instrument_step` can
    report achieved-FLOPs/s and MFU for it (the compile cache later
    refines the estimate with the *compiled* cost model when the same
    program goes through ``load_or_compile``). ``cost_name=None``
    skips registration.
    """
    import contextlib

    from sparkdl_tpu.utils import jax_compat

    if donate_argnums is not None:
        step = jax.jit(step, donate_argnums=tuple(donate_argnums))
    ctx = mesh if mesh is not None else contextlib.nullcontext()
    with ctx:
        lowered = jax_compat.lower(step, *example_args)
    if cost_name is not None:
        from sparkdl_tpu import observe
        from sparkdl_tpu.observe import perf

        if observe.enabled():
            perf.register_step_cost(cost_name, lowered)
    return lowered


def shard_batch(batch, mesh, *, seq_axis=False):
    """Device-put a host batch with (data[, seq]) sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(x):
        if x.ndim >= 2 and seq_axis:
            spec = P(("data", "fsdp"), "seq")
        else:
            spec = P(("data", "fsdp"))
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, batch)


def replicate(tree, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(tree, NamedSharding(mesh, P()))


def cross_entropy_loss(logits, labels, *, ignore_index=None):
    """Token-level softmax cross entropy, fp32 accumulation."""
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if ignore_index is not None:
        mask = labels != ignore_index
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def _chunks(x, chunk, pad):
    """``(B, S, ...)`` as ``(n, B, chunk, ...)``: the scan's axis first,
    the sequence padded at its end to a whole chunk."""
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    b = x.shape[0]
    return x.reshape((b, -1, chunk) + x.shape[2:]).swapaxes(0, 1)


def _chunked(hidden, labels, chunk, ignore_index):
    """What both forms of :func:`fused_cross_entropy` scan, a chunk of
    the sequence at a time: hidden states, labels and the float32 mask
    of the tokens that count; and how many count (of the labels alone)."""
    pad = -hidden.shape[1] % chunk
    valid = jnp.ones(labels.shape, jnp.float32) if ignore_index is None \
        else (labels != ignore_index).astype(jnp.float32)
    return (tuple(_chunks(x, chunk, pad) for x in (hidden, labels, valid)),
            jnp.maximum(valid.sum(), 1.0))


def _chunk_forward(h, w, lbl, matmul_dtype):
    """One chunk's float32 logits, their log-sum-exp and the negative
    log-likelihood of its labels: ``(B, c, V)``, ``(B, c)``, ``(B, c)``."""
    hm = h if matmul_dtype is None else h.astype(matmul_dtype)
    logits = jax.lax.dot_general(
        hm, w, (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lbl[..., None], axis=-1)[..., 0]
    return logits, lse, lse - gold


def _scan_loss(hidden, w_head, labels, chunk, ignore_index, matmul_dtype, *,
               recompute):
    """The mean loss by a scan that keeps no chunk's logits. With
    `recompute` a chunk is a ``jax.checkpoint``, so a gradient taken
    through the scan makes its logits again in the backward pass."""
    xs, count = _chunked(hidden, labels, chunk, ignore_index)
    w = w_head if matmul_dtype is None else w_head.astype(matmul_dtype)

    def chunk_nll(h, lbl):
        return _chunk_forward(h, w, lbl, matmul_dtype)[2]

    if recompute:
        chunk_nll = jax.checkpoint(chunk_nll)

    def body(loss_sum, x):
        h, lbl, m = x
        return loss_sum + (chunk_nll(h, lbl) * m).sum(), None

    loss_sum, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    return loss_sum / count


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _one_pass_loss(hidden, w_head, labels, chunk, ignore_index, matmul_dtype):
    """:func:`fused_cross_entropy` with a frozen head. Alone it is the
    plain scan; under a gradient its forward rule makes the hidden
    states' gradient beside each chunk's logits and keeps that, so the
    head is passed over twice a step and not three times."""
    return _scan_loss(hidden, w_head, labels, chunk, ignore_index,
                      matmul_dtype, recompute=False)


def _one_pass_fwd(hidden, w_head, labels, chunk, ignore_index, matmul_dtype):
    b, s, d = hidden.shape
    xs, count = _chunked(hidden, labels, chunk, ignore_index)
    w = w_head if matmul_dtype is None else w_head.astype(matmul_dtype)

    def body(loss_sum, x):
        h, lbl, m = x
        logits, lse, nll = _chunk_forward(h, w, lbl, matmul_dtype)
        # the loss's gradient in the logits, up to the upstream scalar
        ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        p = jnp.exp(logits - lse[..., None])
        dlogits = jnp.where(ids == lbl[..., None], p - 1.0, p) \
            * (m / count)[..., None]
        # what the transpose of the chunk's product is: float32
        # accumulation, then the product's operand dtype, then hidden's
        dh = jax.lax.dot_general(
            dlogits, w, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if matmul_dtype is not None:
            dh = dh.astype(matmul_dtype)
        return loss_sum + (nll * m).sum(), dh.astype(h.dtype)

    loss_sum, dhc = jax.lax.scan(body, jnp.zeros((), jnp.float32), xs)
    # the one residual: un-padded, in hidden's shape
    return loss_sum / count, dhc.swapaxes(0, 1).reshape(b, -1, d)[:, :s]


def _one_pass_bwd(chunk, ignore_index, matmul_dtype, dhidden, upstream):
    return (upstream * dhidden).astype(dhidden.dtype), None, None


_one_pass_loss.defvjp(_one_pass_fwd, _one_pass_bwd)


@jax.named_scope("sparkdl.lm_head_loss")
def fused_cross_entropy(hidden, w_head, labels, *, chunk_size=256,
                        ignore_index=None, matmul_dtype=None,
                        freeze_head=False):
    """Chunked linear + softmax cross entropy: ``loss = CE(hidden @
    w_head, labels)`` without ever materializing the ``(B, S, V)``
    logits tensor in HBM.

    The sequence axis is scanned in ``chunk_size`` slices; each slice's
    logits live only inside one chunk of the scan. For a 32k vocab at
    batch 8 x seq 1024 this replaces a ~1 GiB fp32 logits round-trip
    (plus its log_softmax twin) with a ~32 MiB working set.

    Two forms, chosen from ``freeze_head``. A frozen head's only
    gradient is the hidden states', ``(softmax - onehot) @ w_head^T``
    up to the scalar from upstream, known the moment a chunk's logits
    are: the forward scan makes it and keeps it (``hidden``'s size),
    and the backward multiplies it by that scalar (``one_pass``: two
    products over the head a step). A trainable head also needs
    ``hidden^T @ dlogits`` summed over the chunks, a float32 ``(D, V)``
    buffer: there ``jax.checkpoint`` makes the backward scan recompute
    each chunk's logits (``recompute``: three products). The
    ``loss.fused`` counter says which form a traced call built.

    :param hidden: ``(B, S, D)`` final hidden states (any float dtype).
    :param w_head: ``(D, V)`` unembedding matrix.
    :param labels: ``(B, S)`` int targets.
    :param chunk_size: tokens per scanned slice of the sequence axis.
    :param ignore_index: label value excluded from the mean.
    :param matmul_dtype: cast both matmul operands (e.g. bf16 halves
        the ``w_head`` HBM read; accumulation stays fp32 via
        ``preferred_element_type``).
    :param freeze_head: the head gets no gradient (LoRA-style frozen
        unembedding): its dW matmul is never emitted, and the loss
        takes the ``one_pass`` form.
    """
    from sparkdl_tpu import observe

    s = hidden.shape[1]
    chunk = min(chunk_size, s)
    # once a traced call: the form the step was built with
    observe.inc("loss.fused", form="one_pass" if freeze_head else "recompute",
                tokens=hidden.shape[0] * s, vocab=w_head.shape[1],
                chunk=chunk, chunks=-(-s // chunk))
    if freeze_head:
        return _one_pass_loss(hidden, w_head, labels, chunk, ignore_index,
                              matmul_dtype)
    return _scan_loss(hidden, w_head, labels, chunk, ignore_index,
                      matmul_dtype, recompute=True)


def global_batch(rng, vocab, batch, seq):
    """Synthetic LM batch (benchmarks and dryruns)."""
    tokens = np.asarray(
        rng.integers(0, vocab, size=(batch, seq + 1)), np.int32
    )
    return {"inputs": tokens[:, :-1], "targets": tokens[:, 1:]}


def make_lm_loss_fn(model, *, loss="logits", chunk=512, ce_bf16=False):
    """The language-model loss closure that both cells of
    ``BENCHMARK.json`` (``chipbench/kinds/train.py``,
    ``train_hybrid.py``) and ``chip_smoke.py`` train through.

    ``loss="logits"``: materialized logits + standard CE.
    ``loss="fused"``: hidden states into :func:`fused_cross_entropy`
    (chunked unembed+CE, frozen head, optional bf16 unembed matmul) —
    the (B,S,V) fp32 logits tensor never hits HBM.
    """
    import jax.numpy as jnp

    if loss == "fused":
        def loss_fn(p, b):
            hidden = model.apply({"params": p}, b["inputs"],
                                 return_hidden=True)
            return fused_cross_entropy(
                hidden, p["lm_head"]["kernel"], b["targets"],
                chunk_size=chunk, freeze_head=True,
                matmul_dtype=jnp.bfloat16 if ce_bf16 else None,
            )
        return loss_fn
    if loss != "logits":
        raise ValueError(f"unknown loss path {loss!r}")

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["inputs"])
        with jax.named_scope("sparkdl.lm_head_loss"):
            return cross_entropy_loss(logits, b["targets"])
    return loss_fn


def param_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def tree_cast(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree
    )
