#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once on ONE TPU chip,
through the entry points a user calls, at Llama-3-8B's published widths
(depth cut, weights random from ``--seed``):

- phase ``train``: ``HorovodRunner(np=1).run(train_main)`` — the
  launcher's real worker process — takes LoRA steps on
  ``parallel.train.make_train_step`` with the flash kernel and checks
  that the loss is finite and falls;
- phase ``serve``: ``ContinuousBatchingEngine`` with the paged cache
  behind ``models/server.py``'s ``POST /generate``, bf16 then int8 then
  int4, each compared with the same engine on the XLA lowerings (and
  bf16 with ``models.generate.generate``) — next-token logits to bf16
  accuracy, tokens equal up to near-ties — and each shown to hold its
  kernels in the decode program.

``python chip_smoke.py --chips 4`` runs instead, and only, the
four-chip path: a ``HorovodRunner(np=4)`` gang with one chip a rank
(collective values, a checkpoint written by rank 0 and read by all, two
data-parallel LoRA steps through ``hvd.grouped_allreduce``), then one
process driving all four chips
through ``parallel.mesh.make_mesh`` with the same seed, and compares
the two.

One process holds a chip at a time: this parent stays off JAX until the
gang's workers have exited. Nothing is chosen by catching an error — no
CPU fallback, no interpret mode, no skipped phase. Every phase prints
one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}`` with the device as JAX reports it,
and it is printed only when every phase passed. Without an accelerator
the script exits nonzero before it prints anything.

The phase functions take a :class:`Spec`; ``tests/test_chip_smoke.py``
rehearses them on the CPU with a tiny one (interpreted kernels).

``chipbench``'s ``correct`` now repeats, every run, phase ``train``'s
checks (finite losses, the kernels in the compiled step) and holds the
loss to a float32 reference; the serve and ``--chips 4`` phases are
checked here alone until a serve cell and a four-chip cell are in.
"""

import argparse
import dataclasses
import functools
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request
import warnings
from typing import Callable, Optional

# Llama-3-8B's published widths (LlamaConfig.llama3_8b); only depth is
# cut. At 4 layers the frozen bf16 base is 3.9 GB (embedding and head
# 1.05 GB each, 0.44 GB a layer), which leaves a 16 GB chip room for
# the step's temporaries beside it and keeps every compile of the two
# phases inside the script's time limit.
N_LAYERS = 4


@dataclasses.dataclass(frozen=True)
class Spec:
    """What the phases run. The defaults are the chip run."""

    platform: str = "tpu"
    seed: int = 0
    # None: LlamaConfig.llama3_8b's widths. Tests pass LlamaConfig.tiny's.
    widths: Optional[dict] = None
    n_layers: int = N_LAYERS
    # kernel mode of the side under test ("auto": the compiled pallas
    # kernels on a TPU); its reference is always "off", the XLA lowering
    kernel: str = "auto"
    # None: LlamaConfig(attention="flash"), the compiled kernel on a TPU
    attention_fn: Optional[Callable] = None
    # train
    batch: int = 4
    seq: int = 2048
    steps: int = 5
    lr: float = 1e-3
    loss_chunk: int = 256
    # serve
    quants: tuple = ("int8", "int4")
    page_size: int = 16
    n_slots: int = 4
    prompt_lens: tuple = (7, 20, 33, 45)   # <1 page, >1 page, 33 crosses
    max_new: int = 32                      # the 32-token prefill bucket
    max_cache_len: int = 128


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _config(spec, **kw):
    import jax.numpy as jnp

    from sparkdl_tpu.models import LlamaConfig

    kw = dict(n_layers=spec.n_layers, dtype=jnp.bfloat16, **kw)
    if spec.widths is None:
        return LlamaConfig.llama3_8b(**kw)
    return LlamaConfig(**{**spec.widths, **kw})


def _init_params(cfg, seed, keep_f32=lambda path: False):
    """Seeded random weights, drawn and cast to bf16 inside one jitted
    program so that the float32 tree never sits on the device whole;
    leaves ``keep_f32`` names (the LoRA adapters) stay float32."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import Llama

    # shapes do not depend on how attention is computed: draw them
    # through the plain path, not through a kernel at sequence length 8
    model = Llama(dataclasses.replace(cfg, attention="reference",
                                      remat=False))

    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x if keep_f32(jax.tree_util.keystr(path))
            else x.astype(jnp.bfloat16), params)

    return jax.jit(init)(jax.random.PRNGKey(seed))


def _lora_setup(spec, attention_fn=None):
    """(model, params, mask, loss_fn) of the LoRA fine-tune both train
    paths share: bf16 frozen base, float32 rank-8 adapters on q and v,
    remat, fused cross-entropy."""
    from sparkdl_tpu.models import Llama, lora_mask
    from sparkdl_tpu.parallel.train import make_lm_loss_fn

    cfg = _config(spec, lora_rank=8, lora_targets=("q_proj", "v_proj"),
                  attention="flash", remat=True)
    model = Llama(cfg, attention_fn=attention_fn or spec.attention_fn)
    params = _init_params(cfg, spec.seed, keep_f32=lambda p: "lora_" in p)
    loss_fn = make_lm_loss_fn(model, loss="fused", chunk=spec.loss_chunk,
                              ce_bf16=True)
    return model, params, lora_mask(params), loss_fn


def _batch(spec, rows=None):
    """The seeded token batch (numpy); ``rows`` selects a rank's share."""
    import numpy as np

    from sparkdl_tpu.parallel.train import global_batch

    vocab = _config(spec).vocab_size
    b = global_batch(np.random.default_rng(spec.seed), vocab,
                     spec.batch, spec.seq)
    return b if rows is None else {k: v[rows] for k, v in b.items()}


_CACHE_COUNTS = {}


def _compile_cache_counts():
    """This process's running counts of persistent compile-cache hits
    and of entries written (a miss that took JAX's threshold, 1 s, or
    longer to compile); JAX reports both as monitoring events. (No
    ``lru_cache`` here: the gang's mains reach this function, and a
    script's functions travel to the workers by value, which a cache
    wrapper cannot.)"""
    import jax

    if not _CACHE_COUNTS:
        _CACHE_COUNTS.update(hits=0, writes=0)

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                _CACHE_COUNTS["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                _CACHE_COUNTS["writes"] += 1

        jax.monitoring.register_event_listener(on_event)
    return _CACHE_COUNTS


def _device_facts(jax):
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _require_platform(jax, spec):
    got = jax.devices()[0].platform
    if got != spec.platform:
        raise RuntimeError(
            f"expected a {spec.platform!r} device, JAX found {got!r}")


# -- phase train -------------------------------------------------------------


def train_main(spec):
    """Runs in the gang worker: ≥ 5 LoRA steps on a repeated batch."""
    warnings.simplefilter("error", RuntimeWarning)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.horovod import log_to_driver
    from sparkdl_tpu.horovod.control_plane import get_worker_client
    from sparkdl_tpu.observe import perf
    from sparkdl_tpu.observe.mem import tree_nbytes
    from sparkdl_tpu.parallel.train import make_train_step

    hvd.init()
    _require_platform(jax, spec)
    cache = _compile_cache_counts()
    dev = jax.devices()[0]
    model, params, mask, loss_fn = _lora_setup(spec)
    opt = optax.masked(optax.adamw(spec.lr), mask)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(loss_fn, opt, param_mask=mask),
                   donate_argnums=(0, 1))
    batch = jax.tree.map(jnp.asarray, _batch(spec))
    param_bytes = tree_nbytes(params)

    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch).compile()
    compile_seconds = time.perf_counter() - t0
    n_kernels = compiled.as_text().count("tpu_custom_call")
    if spec.platform == "tpu" and n_kernels < 3:
        # flash forward, and the dq and dk/dv kernels of its backward
        raise RuntimeError(
            f"the compiled train step holds {n_kernels} tpu_custom_call; "
            "the flash kernel is not in it, forward and backward")

    losses, step_seconds = [], []
    for _ in range(spec.steps):
        t0 = time.perf_counter()
        params, opt_state, metrics = compiled(params, opt_state, batch)
        losses.append(float(jax.block_until_ready(metrics["loss"])))
        step_seconds.append(time.perf_counter() - t0)
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"loss is not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"loss did not fall on a repeated batch: {losses}")

    summed = hvd.allreduce(jnp.arange(8, dtype=jnp.float32), op=hvd.Sum)
    np.testing.assert_array_equal(
        np.asarray(summed), np.arange(8, dtype=np.float32) * hvd.size())
    log_to_driver(
        f"chip_smoke train: rank {hvd.rank()}/{hvd.size()} on "
        f"{dev.device_kind}, loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    client = get_worker_client()
    return {
        "loss0": losses[0], "lossN": losses[-1], "losses": losses,
        "device_kind": dev.device_kind,
        "peaks_key": perf.normalize_device_kind(dev.device_kind),
        "n_devices": len(jax.devices()), "hvd_size": hvd.size(),
        "n_layers": spec.n_layers, "batch": spec.batch, "seq": spec.seq,
        "param_bytes": param_bytes,
        "peak_bytes_in_use": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use"),
        "tpu_custom_calls": n_kernels,
        "compile_seconds": compile_seconds, "step_seconds": step_seconds,
        "compile_cache": dict(cache),
        "log_transport": client.log_transport if client else "none",
    }


def phase_train(spec):
    from sparkdl import HorovodRunner

    t0 = time.perf_counter()
    out = HorovodRunner(np=1).run(train_main, spec=spec)
    emit("train", ok=True, seconds=time.perf_counter() - t0, **out)
    return out


# -- phase serve -------------------------------------------------------------


def _post_generate(address, prompts, max_new):
    """One concurrent POST /generate a prompt; the token lists, in
    prompt order."""
    out, errors = [None] * len(prompts), []

    def post(i):
        try:
            req = urllib.request.Request(
                f"http://{address[0]}:{address[1]}/generate",
                data=json.dumps({"tokens": [int(t) for t in prompts[i]],
                                 "max_new_tokens": max_new}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=900) as resp:
                out[i] = json.loads(resp.read())["tokens"]
        except Exception as e:  # re-raised below, on the caller's thread
            errors.append(e)

    threads = [threading.Thread(target=post, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


def _serve(engine, prompts, max_new):
    from sparkdl_tpu.models.server import ServingFrontend

    frontend = ServingFrontend(engine).start()
    try:
        return _post_generate(frontend.address, prompts, max_new)
    finally:
        frontend.close()


@functools.lru_cache(maxsize=None)
def _probe_programs(cfg):
    """(prefill, step) of an engine's decode model, jitted: prefill a
    padded prefix through the paged cache (the XLA path at every
    kernel mode — the kernels serve single-token steps), then ONE
    decode step, the step the kernels serve, returning its logits."""
    import jax

    from sparkdl_tpu.models import Llama

    model = Llama(cfg)

    @jax.jit
    def prefill(params, cache, tokens, tables):
        import jax.numpy as jnp

        _, st = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=jnp.arange(tokens.shape[1])[None],
            block_tables=tables, mutable=["cache"])
        return st["cache"]

    @jax.jit
    def step(params, cache, token, pos, tables):
        logits, _ = model.apply(
            {"params": params, "cache": cache}, token[None, None],
            positions=pos[None, None], block_tables=tables,
            mutable=["cache"])
        return logits[0, -1]

    return prefill, step


def _next_logits(engine, tokens):
    """Logits of the token after ``tokens`` as ``engine``'s decode
    program computes them (float32 numpy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = engine.cfg
    prefill, step = _probe_programs(cfg)
    n_pg = -(-cfg.max_cache_len // cfg.page_size)
    tables = jnp.arange(1, n_pg + 1, dtype=jnp.int32)[None]  # 0: dump page
    cache = jax.tree.map(jnp.zeros_like, engine._cache)
    # pad rows land at positions the step never sees: it overwrites the
    # row at len-1 and attends to nothing beyond it
    padded = np.zeros((1, cfg.max_cache_len), np.int32)
    padded[0, :len(tokens) - 1] = tokens[:-1]
    cache = prefill(engine.params, cache, jnp.asarray(padded), tables)
    return np.asarray(step(
        engine.params, cache, jnp.asarray(tokens[-1], jnp.int32),
        jnp.asarray(len(tokens) - 1, jnp.int32), tables), np.float32)


@functools.lru_cache(maxsize=None)
def _oracle_program(cfg):
    import jax

    from sparkdl_tpu.models import Llama

    model = Llama(cfg)
    return jax.jit(lambda params, tokens, last: model.apply(
        {"params": params}, tokens)[0, last])


def _oracle_logits(cfg, params, tokens):
    """Logits of the token after ``tokens`` as the single-stream model
    ``generate()`` decodes with computes them: one causal forward over
    the padded prefix, no cache and no page table."""
    import jax.numpy as jnp
    import numpy as np

    padded = np.zeros((1, cfg.max_cache_len), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(_oracle_program(cfg)(
        params, jnp.asarray(padded),
        jnp.asarray(len(tokens) - 1, jnp.int32)), np.float32)


# Two right lowerings of one bf16 program round the hidden state in
# different orders, and bf16 keeps 8 bits of it: their logits differ by
# a few parts in 2**8 of the largest logit. On the chip the largest
# kernel-vs-XLA difference over the vocabulary was 0.042-0.064 against
# a largest logit of about 4.3-4.9, 2.4-3.8 parts (my chip run and my
# CPU reading of the same seeded weights, PR 21). A kernel that
# computes something else is off by the logits' own size, some thirty
# times this bound.
LOGIT_TOL = 8 * 2.0 ** -8


def _compare(name, prompts, got, want, kern_logits, ref_logits):
    """The side under test against its reference, both as token lists
    and as ``tokens -> next-token logits`` functions.

    Logits first, because greedy tokens of random weights cannot tell a
    wrong kernel from a rounding difference: after every prompt, and at
    the first position where a request's tokens differ, the two logit
    vectors agree over the whole vocabulary to ``LOGIT_TOL`` of the
    largest logit. Then the tokens are equal — or that first difference
    is a near-tie: the reference prefers its token ``w`` to the other
    side's ``g`` by a margin that the measured difference ``d`` can
    turn, ``ref[w] - ref[g] <= 2 d`` (each of the two logits moves by
    at most ``d``, and ``d`` is held to the bound above, so a kernel
    cannot buy the waiver by being more wrong). Raises on anything
    else; returns what it saw."""
    import numpy as np

    seen = {"logit_checks": [], "near_ties": []}
    for prompt, g, w in zip(prompts, got, want):
        g, w = list(g), list(w)
        if len(g) != len(w):
            raise RuntimeError(f"{name}: {len(g)} tokens against {len(w)}")
        at = next((i for i, (a, b) in enumerate(zip(g, w)) if a != b), None)
        for position in sorted({0, at} - {None}):
            prefix = np.concatenate(
                [prompt, np.asarray(g[:position], np.int32)])
            ref, kern = ref_logits(prefix), kern_logits(prefix)
            diff = float(np.abs(kern - ref).max())
            bound = LOGIT_TOL * float(np.abs(ref).max())
            where = (f"{name}: after {position} new token(s) of the "
                     f"{len(prompt)}-token prompt")
            if not diff <= bound:   # a nan fails too
                raise RuntimeError(
                    f"{where} the logits differ by {diff}, more than "
                    f"{bound} ({LOGIT_TOL} of the largest): the side "
                    "under test computes something else")
            seen["logit_checks"].append(
                {"prompt_len": len(prompt), "position": position,
                 "max_logit_diff": diff, "bound": bound})
            if position != at:
                continue
            margin = float(ref[w[at]] - ref[g[at]])
            seen["near_ties"].append(
                {"prompt_len": len(prompt), "position": at,
                 "tokens": [int(g[at]), int(w[at])], "ref_margin": margin,
                 "max_logit_diff": diff,
                 "diff_on_tokens": [float(kern[g[at]] - ref[g[at]]),
                                    float(kern[w[at]] - ref[w[at]])]})
            if not margin <= 2 * diff:
                raise RuntimeError(
                    f"{where} the tokens diverge ({g[at]} against "
                    f"{w[at]}) and it is no near-tie: the reference "
                    f"prefers its own by {margin}, more than twice the "
                    f"logit difference {diff}")
    return seen


def _engine(spec, cfg, params):
    from sparkdl_tpu.models import Llama
    from sparkdl_tpu.models.serving import ContinuousBatchingEngine

    return ContinuousBatchingEngine(
        Llama(cfg), params, n_slots=spec.n_slots, page_size=spec.page_size,
        chunk=16)


def _kernels(engine):
    """How many pallas kernels the engine's decode program holds."""
    return engine.lower_decode_chunk().as_text().count("tpu_custom_call")


def _serve_bf16(spec, cfg, params, prompts):
    """The paged kernel against the gather path and the single-stream
    oracle; returns the kernel count of the paged program."""
    import numpy as np

    from sparkdl_tpu.models import Llama
    from sparkdl_tpu.models.generate import generate
    from sparkdl_tpu.observe.mem import tree_nbytes

    t0 = time.perf_counter()
    kern = _engine(
        spec, dataclasses.replace(cfg, paged_kernel=spec.kernel), params)
    ref = _engine(spec, dataclasses.replace(cfg, paged_kernel="off"), params)
    got = _serve(kern, prompts, spec.max_new)
    if any(len(g) != spec.max_new for g in got):
        raise RuntimeError(f"short answers: {[len(g) for g in got]}")
    oracle = [
        np.asarray(generate(Llama(cfg), params, p[None],
                            max_new_tokens=spec.max_new))[0, len(p):]
        for p in prompts]
    kern_logits = functools.partial(_next_logits, kern)
    vs_oracle = _compare(
        "bf16 paged kernel vs generate()", prompts, got, oracle,
        kern_logits, functools.partial(_oracle_logits, cfg, params))
    vs_off = _compare(
        "bf16 paged kernel vs paged_kernel=off", prompts, got,
        _serve(ref, prompts, spec.max_new), kern_logits,
        functools.partial(_next_logits, ref))
    n_paged, n_off = _kernels(kern), _kernels(ref)
    if n_off or (spec.platform == "tpu" and not n_paged):
        raise RuntimeError(
            f"decode program kernels: paged_kernel={spec.kernel!r} holds "
            f"{n_paged} tpu_custom_call, 'off' holds {n_off}")
    emit("serve", weights="bf16", ok=True, requests=len(prompts),
         prompt_lens=list(spec.prompt_lens), new_tokens=spec.max_new,
         **{k: vs_oracle[k] + vs_off[k] for k in vs_oracle},
         tpu_custom_calls=n_paged,
         tpu_custom_calls_off=n_off, param_bytes=tree_nbytes(params),
         seconds=time.perf_counter() - t0)
    return n_paged


def _serve_quant(spec, cfg, params, prompts, quant, n_paged):
    """The fused quant matmul against the XLA dequant lowering; the
    paged kernel stays on both sides."""
    import jax

    from sparkdl_tpu.models.quant import quantize_llama_params
    from sparkdl_tpu.observe.mem import tree_nbytes

    t0 = time.perf_counter()
    cfg = dataclasses.replace(cfg, quant=quant, paged_kernel=spec.kernel)
    qparams = jax.device_put(quantize_llama_params(
        params, bits=int(quant[3:]), group=cfg.quant_group))
    kern = _engine(
        spec, dataclasses.replace(cfg, quant_kernel=spec.kernel), qparams)
    ref = _engine(spec, dataclasses.replace(cfg, quant_kernel="off"), qparams)
    got = _serve(kern, prompts, spec.max_new)
    seen = _compare(
        f"{quant} quant kernel vs quant_kernel=off", prompts, got,
        _serve(ref, prompts, spec.max_new),
        functools.partial(_next_logits, kern),
        functools.partial(_next_logits, ref))
    n_quant, n_ref = _kernels(kern), _kernels(ref)
    if n_ref != n_paged or (spec.platform == "tpu"
                            and not n_quant > n_paged):
        raise RuntimeError(
            f"{quant} decode program kernels: quant_kernel={spec.kernel!r} "
            f"holds {n_quant} tpu_custom_call, 'off' holds {n_ref}, the "
            f"paged kernel alone {n_paged}")
    emit("serve", weights=quant, ok=True, requests=len(prompts),
         new_tokens=spec.max_new, **seen, tpu_custom_calls=n_quant,
         tpu_custom_calls_off=n_ref,
         param_bytes=tree_nbytes(qparams), seconds=time.perf_counter() - t0)


def phase_serve(spec):
    import jax
    import numpy as np

    _require_platform(jax, spec)
    t0 = time.perf_counter()
    cache = _compile_cache_counts()
    seen = dict(cache)
    cfg = _config(spec, max_cache_len=spec.max_cache_len)
    params = _init_params(cfg, spec.seed + 1)
    rng = np.random.default_rng(spec.seed + 2)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in spec.prompt_lens]
    n_paged = _serve_bf16(spec, cfg, params, prompts)
    # one variant's engines and weights are gone before the next
    # one's arrive: each call's locals die with it
    for quant in spec.quants:
        _serve_quant(spec, cfg, params, prompts, quant, n_paged)
    emit("serve", ok=True, n_layers=spec.n_layers,
         peak_bytes_in_use=(jax.devices()[0].memory_stats() or {}).get(
             "peak_bytes_in_use"),
         compile_cache={k: cache[k] - seen[k] for k in cache},
         seconds=time.perf_counter() - t0)


# -- four chips --------------------------------------------------------------


def _dp_programs(spec, loss_fn, mask, opt):
    """(grads, update) of a data-parallel LoRA step whose gradients are
    averaged BETWEEN the two programs: by ``hvd.grouped_allreduce`` in
    the gang, by nothing under a mesh (GSPMD already reduced them)."""
    import jax
    import optax

    def split(params):
        flat, treedef = jax.tree.flatten(params)
        keep = treedef.flatten_up_to(mask)
        return flat, keep, treedef

    @jax.jit
    def grads(params, batch):
        flat, keep, treedef = split(params)

        def loss_of(trainable):
            it = iter(trainable)
            merged = [next(it) if k else jax.lax.stop_gradient(p)
                      for p, k in zip(flat, keep)]
            return loss_fn(treedef.unflatten(merged), batch)

        return jax.value_and_grad(loss_of)(
            [p for p, k in zip(flat, keep) if k])

    @jax.jit
    def update(params, opt_state, g):
        flat, keep, treedef = split(params)
        it = iter(g)
        full = treedef.unflatten(
            [next(it) if k else p for p, k in zip(flat, keep)])
        updates, opt_state = opt.update(full, opt_state, params)
        flat_u = treedef.flatten_up_to(updates)
        new = treedef.unflatten(
            [p + u if k else p for p, u, k in zip(flat, flat_u, keep)])
        return new, opt_state, optax.global_norm(g)

    return grads, update


def gang_main(spec, ckpt_dir):
    """Runs in each of the four gang workers, one chip each."""
    warnings.simplefilter("error", RuntimeWarning)
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import sparkdl_tpu.hvd as hvd
    from sparkdl_tpu.utils.checkpoint import (
        TrainCheckpointer,
        load_sharding_tree,
    )

    hvd.init()
    _require_platform(jax, spec)
    rank, size = hvd.rank(), hvd.size()
    local = jax.local_devices()
    if size != 4 or len(local) != 1:
        raise RuntimeError(
            f"rank {rank}: hvd.size()={size}, {len(local)} local devices; "
            "a four-chip gang is four ranks of one chip each")
    dev = local[0]
    ids = hvd.allgather_object(dev.id)
    if len(set(ids)) != 4:
        raise RuntimeError(f"ranks do not hold four distinct chips: {ids}")

    x = jax.device_put(jnp.full((8,), rank + 1.0, jnp.float32), dev)
    np.testing.assert_array_equal(
        np.asarray(hvd.allreduce(x, op=hvd.Sum)), np.full((8,), 10.0))
    np.testing.assert_array_equal(
        np.asarray(hvd.allreduce(x, op=hvd.Average)), np.full((8,), 2.5))
    np.testing.assert_array_equal(
        np.asarray(hvd.allgather(x[:2])), np.repeat([1., 2., 3., 4.], 2))
    np.testing.assert_array_equal(
        np.asarray(hvd.broadcast(x, root_rank=2)), np.full((8,), 3.0))

    # Rank 0 writes the step and its sharding sidecar, whatever index
    # the runtime gave its process; every rank then reads it back.
    # (Host values: a gang saves its replicated state from the host.)
    ckpt = TrainCheckpointer(ckpt_dir)
    wrote = ckpt.save(
        1, {"sum": np.asarray(hvd.allreduce(x, op=hvd.Sum))})
    hvd.barrier()
    if wrote != (rank == 0) or ckpt.latest_step() != 1 \
            or load_sharding_tree(ckpt_dir, 1) is None:
        raise RuntimeError(
            f"rank {rank} (process {jax.process_index()}): save returned "
            f"{wrote}, latest step {ckpt.latest_step()}, sidecar "
            f"{load_sharding_tree(ckpt_dir, 1) is not None}")
    np.testing.assert_array_equal(
        ckpt.restore(1, target={"sum": np.zeros((8,), np.float32)})["sum"],
        np.full((8,), 10.0))
    ckpt.close()

    _, params, mask, loss_fn = _lora_setup(spec)
    opt = optax.masked(optax.adamw(spec.lr), mask)
    opt_state = opt.init(params)
    grads, update = _dp_programs(spec, loss_fn, mask, opt)
    per = spec.batch // size
    batch = jax.tree.map(
        jnp.asarray, _batch(spec, slice(rank * per, (rank + 1) * per)))
    losses, norms = [], []
    for _ in range(2):
        loss, g = grads(params, batch)
        g = hvd.grouped_allreduce(g, op=hvd.Average)
        params, opt_state, norm = update(params, opt_state, g)
        losses.append(float(hvd.allreduce(loss, op=hvd.Average)))
        norms.append(float(norm))
    return {"losses": losses, "grad_norms": norms, "device_ids": ids,
            "process_index_by_rank": hvd.allgather_object(
                jax.process_index()),
            "device_kind": dev.device_kind,
            "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                "peak_bytes_in_use")}


def mesh_reference(spec):
    """The same two steps in ONE process over all four chips: batch
    sharded over ``data``, parameters replicated."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from sparkdl_tpu.ops.attention import flash_attention
    from sparkdl_tpu.parallel.mesh import MeshSpec, make_mesh
    from sparkdl_tpu.parallel.train import replicate, shard_batch

    _require_platform(jax, spec)
    mesh = make_mesh(MeshSpec(data=4))
    # GSPMD cannot partition a pallas call: bind the kernel to the mesh
    # over the batch axis, each device attending to its own rows
    rows = P(("data", "fsdp"))
    attend = jax.shard_map(
        spec.attention_fn or functools.partial(flash_attention, causal=True),
        mesh=mesh, in_specs=(rows, rows, rows), out_specs=rows,
        check_vma=False)
    _, params, mask, loss_fn = _lora_setup(spec, attention_fn=attend)
    opt = optax.masked(optax.adamw(spec.lr), mask)
    grads, update = _dp_programs(spec, loss_fn, mask, opt)
    with mesh:
        params = replicate(params, mesh)
        opt_state = replicate(opt.init(params), mesh)
        batch = shard_batch(_batch(spec), mesh)
        losses, norms = [], []
        for _ in range(2):
            loss, g = grads(params, batch)
            params, opt_state, norm = update(params, opt_state, g)
            losses.append(float(loss))
            norms.append(float(norm))
    return {"losses": losses, "grad_norms": norms}


def phase_four_chips(spec, tol=2e-2):
    import numpy as np

    from sparkdl import HorovodRunner

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-ckpt-") as ckpt_dir:
        gang = HorovodRunner(np=4).run(
            gang_main, spec=spec, ckpt_dir=ckpt_dir)
    emit("gang", ok=True, seconds=time.perf_counter() - t0, **gang)
    # the gang's workers have exited: this process may take the chips
    t0 = time.perf_counter()
    ref = mesh_reference(spec)
    emit("mesh", ok=True, seconds=time.perf_counter() - t0, **ref)
    for key in ("losses", "grad_norms"):
        np.testing.assert_allclose(
            gang[key], ref[key], rtol=tol,
            err_msg=f"{key}: the four-rank gang against one process "
                    "over a four-device mesh")
    emit("four_chips", ok=True, rtol=tol)


# -- the script --------------------------------------------------------------


def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the four-chip path and its reference, only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    warnings.simplefilter("error", RuntimeWarning)

    from sparkdl_tpu.horovod import launcher
    from sparkdl_tpu.parallel.compile import export_cache_dir

    # Before jax is imported anywhere: where the compile cache lives,
    # for this process and the workers that inherit its environment.
    cache_dir = export_cache_dir()
    entries_before = _cache_entries(cache_dir)
    # What is attached, without starting a runtime in this process
    # (the launcher's own slot probe; its answer is kept).
    local = launcher.probe_local_devices(
        os.environ.get(launcher.WORKER_PLATFORM_ENV))
    if local.platform != "tpu" or local.count < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX finds "
              f"{local.count} {local.platform!r} device(s)", file=sys.stderr)
        return 2
    spec = Spec(seed=args.seed)
    emit("start", chips=args.chips, n_layers=spec.n_layers,
         compile_cache_dir=cache_dir, cache_entries_before=entries_before,
         local_devices=local._asdict())

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(spec)
    else:
        phase_train(spec)
        # the worker has exited and been reaped: the chip is free
        phase_serve(spec)

    import jax

    device = _device_facts(jax)
    if device["count"] != args.chips:
        raise RuntimeError(f"ran for {args.chips} chip(s) on {device}")
    emit("end", seconds=time.perf_counter() - t0,
         compile_cache_dir=cache_dir, cache_entries_before=entries_before,
         cache_entries_after=_cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
