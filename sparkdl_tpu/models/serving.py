"""Continuous-batching decode engine: slot-mapped KV cache, per-slot
positions, admission of new sequences between decode chunks.

Single-stream serving (models/generate.py) leaves the chip idle
whenever one sequence finishes before another would start; production
serving interleaves many requests through a fixed set of batch SLOTS
(vLLM-style iteration-level scheduling, re-thought for XLA):

- The KV cache is one batched pytree with leading dim = n_slots; slot
  ``i``'s rows belong to whichever request currently occupies it.
- Every decode step runs ONE jitted program over all slots with an
  explicit per-slot position vector (``positions`` in the model's
  decode path — the slot-mapped branch in ``models/llama.py``).
- Python-level scheduling happens only every ``chunk`` tokens: the
  decode loop is a ``lax.scan`` (per-token host dispatch would pay a
  dispatch and a readback per token), so admission granularity is the
  chunk, a deliberate XLA-first trade-off against per-iteration
  admission.
- Admission: a finished slot is refilled by PREFILLING the queued
  request's prompt (bucket-padded to bound recompiles; the sampled
  first token is taken at the true prompt end) and inserting its cache
  rows, position, and first token into the batched state.

Inactive slots keep decoding junk into their frozen position — one
overwritten, never-visible cache row — which costs nothing extra on
the MXU (the batch dim is fixed) and keeps every program shape static.

No reference counterpart (the reference is a training-launcher stub);
this is the serving-depth side of SURVEY.md §2's model-zoo story.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


def _bucket(n, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)):
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest bucket")


def _hits_stop(tokens, stops):
    """True when any stop sequence is a suffix of ``tokens``."""
    return any(len(tokens) >= len(st)
               and tuple(tokens[-len(st):]) == st for st in stops)


def _pad_bucket(tokens, cap):
    """Bucket-pad a 1-D token array to ``min(_bucket(len), cap)`` as a
    (1, bucket) int32 batch — ONE definition of the prefill padding
    policy (target + draft, full prompts + suffixes)."""
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    bucket = min(_bucket(len(tokens)), cap)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(tokens)] = tokens
    return padded


@functools.lru_cache(maxsize=64)
def _engine_programs(dec_cfg, temperature, sharded_mesh=None, top_k=0,
                     top_p=1.0):
    """(prefill, suffix_prefill, paged_prefill, insert, decode_chunk,
    copy_pages)
    — positional order is load-bearing (the engine's _programs[i]
    properties index it) — jitted once per (decode config,
    temperature, sharded mesh) — module-level like
    generate._decode_programs, so a fresh engine instance reuses
    compiled programs instead of paying XLA again (an engine per
    request burst is the normal usage).

    ``sharded_mesh``: a TP mesh to bind the paged decode kernel to
    (shard_map over the kv-head axis) — set by the engine only when
    the cache is actually head-sharded and the kernel mode is on."""
    from sparkdl_tpu.models.llama import Llama

    paged_fn = None
    if sharded_mesh is not None:
        from sparkdl_tpu.ops.pallas.paged_attention import (
            paged_attention_decode_sharded,
        )

        paged_fn = paged_attention_decode_sharded(
            sharded_mesh, axis_name="model",
            interpret=(dec_cfg.paged_kernel == "force_interpret"),
        )
    model = Llama(dec_cfg, paged_attention_fn=paged_fn)

    def _sample(logits, rng):
        from sparkdl_tpu.models.generate import sample_logits

        return sample_logits(logits, rng, temperature=temperature,
                             top_k=top_k, top_p=top_p)

    def _sample_lp(logits, rng):
        from sparkdl_tpu.models.generate import sample_logits_with_lp

        return sample_logits_with_lp(
            logits, rng, temperature=temperature, top_k=top_k,
            top_p=top_p)

    @jax.jit
    def prefill(params, padded_prompt, rng, true_len, adapter_ids=None):
        # standard shared-index decode-mode prefill, batch 1; junk pad
        # rows land at positions >= true_len where the causal cache
        # mask keeps them invisible until overwritten. true_len is a
        # TRACED scalar: one compile per bucket, not per prompt length.
        logits, state = model.apply(
            {"params": params}, padded_prompt,
            adapter_ids=adapter_ids, mutable=["cache"],
        )
        last = logits[:, true_len - 1]
        tok, lp = _sample_lp(last, rng)
        return state["cache"], tok, lp

    @jax.jit
    def suffix_prefill(params, prefix_cache, padded_suffix, rng,
                       true_len, adapter_ids=None):
        # prefix caching: continue a STORED prefix cache (its shared
        # index already sits at the prefix length) over the request's
        # suffix only — the prefix rows are copied, never recomputed
        logits, state = model.apply(
            {"params": params, "cache": prefix_cache}, padded_suffix,
            adapter_ids=adapter_ids, mutable=["cache"],
        )
        last = logits[:, true_len - 1]
        tok, lp = _sample_lp(last, rng)
        return state["cache"], tok, lp

    @functools.partial(jax.jit, donate_argnums=(1,))
    def paged_prefill(params, cache, padded_prompt, table_row, rng,
                      true_len, start_pos, adapter_ids=None):
        """Paged admission: prefill writes STRAIGHT into the pooled
        physical cache through this slot's block table — there is no
        per-slot cache to copy afterwards. ``start_pos`` supports
        future prefix reuse (0 today)."""
        s = padded_prompt.shape[1]
        positions = start_pos + jnp.arange(s)[None, :]
        logits, state = model.apply(
            {"params": params, "cache": cache}, padded_prompt,
            positions=positions, block_tables=table_row,
            adapter_ids=adapter_ids, mutable=["cache"],
        )
        last = logits[:, true_len - 1]
        tok, lp = _sample_lp(last, rng)
        return state["cache"], tok, lp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def copy_pages(cache, src_pages, dst_pages):
        """Copy physical pages src->dst inside the pool (paged prefix
        sharing: the PARTIAL boundary page of a shared prefix must be
        per-slot — a suffix starting mid-page writes into it)."""
        def leaf(x):
            if x.ndim != 4:  # scalar cache_index leaves pass through
                return x
            return x.at[dst_pages].set(x[src_pages])

        return jax.tree.map(leaf, cache)

    @jax.jit
    def insert(cache, pos, token, one_cache, new_token, p_len, slot):
        # scalar leaves (the shared cache_index, unused on the
        # slot-mapped path) pass through; K/V rows land in the slot
        cache = jax.tree.map(
            lambda full, one: (
                full if full.ndim == 0 else full.at[slot].set(one[0])
            ),
            cache, one_cache,
        )
        return (cache, pos.at[slot].set(p_len),
                token.at[slot].set(new_token[0]))

    @functools.partial(jax.jit, static_argnums=(6,),
                       donate_argnums=(1,))
    def decode_chunk(params, cache, token, pos, active, rng, n,
                     tables=None, adapter_ids=None):
        def body(carry, _):
            cache, token, pos, rng = carry
            logits, st = model.apply(
                {"params": params, "cache": cache},
                token[:, None], positions=pos[:, None],
                block_tables=tables, adapter_ids=adapter_ids,
                mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt, lp = _sample_lp(logits[:, -1], sub)
            # inactive slots freeze: position pinned (their junk
            # write is overwritten in place, never visible). Active
            # slots clamp at the last cache row: chunk lengths round
            # up to a power of two, so a slot whose budget ends
            # mid-chunk keeps stepping — without the clamp its writes
            # would pass max_cache_len (out of bounds for the dense
            # scatter, junk into a neighbour's page when paged). The
            # overshot tokens are discarded host-side.
            pos = jnp.where(
                active,
                jnp.minimum(pos + 1, dec_cfg.max_cache_len - 1),
                pos)
            return (st["cache"], nxt, pos, rng), (nxt, lp)

        (cache, token, pos, rng), (toks, lps) = jax.lax.scan(
            body, (cache, token, pos, rng), None, length=n
        )
        return cache, token, pos, rng, toks, lps  # (n, n_slots) each

    return (prefill, suffix_prefill, paged_prefill, insert,
            decode_chunk, copy_pages)


@dataclasses.dataclass
class _Slot:
    req_id: int = -1
    active: bool = False
    remaining: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    logprobs: list = dataclasses.field(default_factory=list)


class ContinuousBatchingEngine:
    """Greedy/temperature decoding over ``n_slots`` concurrent streams.

    Usage::

        eng = ContinuousBatchingEngine(model, params, n_slots=4)
        rid = eng.submit(prompt_tokens_1d, max_new_tokens=64)
        results = eng.run()          # {rid: np.ndarray of new tokens}

    ``stats`` afterwards holds steps, slot-step counts, and the slot
    utilization ratio (active slot-steps / total slot-steps).
    """

    def __init__(self, model, params, *, n_slots=4, temperature=0.0,
                 eos_id=None, chunk=16, rng=None, mesh=None,
                 rules=None, page_size=0, n_pages=None,
                 prefill_chunk=0, top_k=0, top_p=1.0, quant="",
                 quant_kernel=""):
        """``mesh`` enables tensor-parallel serving: params are placed
        per ``rules`` (default TRANSFORMER_RULES — Megatron column/row
        splits) and the KV cache is sharded over its kv-heads axis on
        the ``model`` mesh axis; GSPMD inserts the collectives in the
        same jitted programs the single-device engine runs.

        ``quant`` ("int8" | "int4") selects weight-only quantized
        serving PER ENGINE: the dense ``params`` tree is quantized at
        construction (models.quant.quantize_llama_params) and every
        decode matmul runs through QuantDense/QuantDense4 — one fleet
        can mix bf16 and int8 replicas off the same checkpoint.
        Composes with ``mesh``: the sharding rules match the
        ``kernel_q``/``kernel_q4`` leaves through the same Megatron
        patterns as dense kernels (scales replicate). Pass a tree
        that is ALREADY quantized (cfg.quant set on ``model``) with
        ``quant=""`` — quantizing twice is refused.

        ``quant_kernel`` routes the engine's dequant GEMMs: "" defers
        to the ``SPARKDL_TPU_KERNEL_QUANT_MATMUL`` knob, "auto" runs
        the fused pallas quant-matmul on TPU (XLA dequant elsewhere),
        "off" pins the XLA lowering, "force_interpret" emulates the
        kernel on any backend (the token-exactness oracle). Becomes
        ``cfg.quant_kernel``, so it is part of the engine's program
        cache key.

        ``page_size`` > 0 switches to a PAGED KV cache: one pooled
        physical store of ``n_pages`` pages shared by every slot
        through per-slot block tables, so memory is sized to the POOL
        (actual concurrent context), not n_slots × max_cache_len.
        Admission allocates a request's worst-case pages up front and
        queues the request when the pool is exhausted (capacity
        admission control); a finished request's pages return to the
        pool. Page 0 is a write-only dump for bucket-padding junk.
        Default ``n_pages`` reproduces dense capacity exactly.

        ``prefill_chunk`` (paged only): prompts longer than this
        prefill in segments interleaved with decode chunks
        (Sarathi-style), bounding the decode stall a long admission
        causes to one segment instead of the whole prompt."""
        cfg = model.cfg
        if quant:
            if quant not in ("int8", "int4"):
                raise ValueError(
                    f"unknown quant mode {quant!r}; expected 'int8' "
                    "or 'int4'"
                )
            if cfg.quant:
                raise ValueError(
                    f"model is already quantized (cfg.quant="
                    f"{cfg.quant!r}); pass quant= only with a dense "
                    "tree"
                )
            from sparkdl_tpu.models.quant import quantize_llama_params

            # replace() re-runs __post_init__, which enforces the
            # quant/LoRA/multi-adapter exclusivity rules
            cfg = dataclasses.replace(cfg, quant=quant)
            params = quantize_llama_params(
                params, bits=8 if quant == "int8" else 4,
                group=cfg.quant_group)
        if quant_kernel:
            if not cfg.quant:
                raise ValueError(
                    "quant_kernel routes the dequant GEMMs of a "
                    "quantized engine; pass quant= (or a quantized "
                    "model) with it")
            cfg = dataclasses.replace(cfg, quant_kernel=quant_kernel)
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {self.prefill_chunk}"
            )
        if self.prefill_chunk and not self.page_size:
            raise ValueError(
                "prefill_chunk requires the paged cache (page_size>0): "
                "the dense slot cache has no per-slot write path for "
                "partial prompts"
            )
        self._prefilling = {}  # slot -> staged chunked-prefill state
        self._on_token = None  # streaming callback, set per run()
        self._max_pages = (
            -(-cfg.max_cache_len // self.page_size) if page_size else 0)
        self._paged_sharded_mesh = None  # set only by the TP+kernel path
        if page_size:
            n_pages = (int(n_pages) if n_pages is not None
                       else int(n_slots) * self._max_pages + 1)
            cfg = dataclasses.replace(
                cfg, page_size=self.page_size, n_pages=n_pages)
            if mesh is not None and cfg.paged_kernel != "off":
                # A raw pallas_call cannot be partitioned by GSPMD, so
                # under TP the kernel runs through its shard_map
                # binding over the kv-head axis (one kernel per shard,
                # no collectives — GQA query groups are co-resident
                # with their kv heads). Engage only when the cache is
                # actually head-sharded (divisibility) and the kernel
                # would run at all. Off the TPU "auto" means the
                # gather path anyway, which GSPMD shards fine; on a
                # TPU a kernel that cannot engage is an error, not a
                # quiet switch to the path the caller did not ask for.
                from sparkdl_tpu.ops._dispatch import use_pallas

                model_size = dict(mesh.shape).get("model", 0)
                head_sharded = (model_size > 0
                                and cfg.n_kv_heads % model_size == 0)
                if head_sharded and (
                        cfg.paged_kernel == "force_interpret"
                        or use_pallas()):
                    self._paged_sharded_mesh = mesh
                elif cfg.paged_kernel == "auto":
                    if use_pallas():
                        raise ValueError(
                            f"paged_kernel='auto' under a mesh needs "
                            f"n_kv_heads ({cfg.n_kv_heads}) divisible "
                            f"by its 'model' axis ({model_size}); pass "
                            "paged_kernel='off' for the gather path")
                    cfg = dataclasses.replace(cfg, paged_kernel="off")
                # an explicit force_interpret stays: with kv heads not
                # divisible the cache_spec REPLICATES the pool, where
                # the raw (unsharded) kernel call is valid — never
                # silently downgrade a user's explicit kernel mode
        self.cfg = dataclasses.replace(cfg, decode=True)
        self.n_slots = int(n_slots)
        self.temperature = float(temperature)
        # sampling restrictions (temperature > 0): top_k keeps the k
        # most likely tokens, top_p the minimal nucleus reaching p
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = eos_id
        self.chunk = int(chunk)
        self._rng = rng if rng is not None else jax.random.PRNGKey(0)
        from sparkdl_tpu.models.llama import Llama

        self._model = Llama(self.cfg)
        self._queue = []    # (rid, prompt, max_new, prefix_id,
                            #  adapter_id)
        self._prefixes = {}  # prefix_id -> (tokens,
                             #   cache | pool pages, adapter_id)
        self._slots = [_Slot() for _ in range(self.n_slots)]
        self._results = {}
        self._stops = {}           # rid -> tuple of stop token tuples
        self._finish_reasons = {}  # rid -> "eos" | "length" | "stop"
        self.finish_reasons = {}   # last drained burst's reasons
        self._logprobs = {}        # rid -> finished logprob array
        self.logprobs = {}         # last drained burst's logprobs
        self._next_id = 0
        self.stats = {"steps": 0, "active_slot_steps": 0,
                      "total_slot_steps": 0}
        # Request-level observability hook (a ServingTelemetry from
        # sparkdl_tpu.observe.serving, installed by the HTTP frontend
        # only when SPARKDL_TPU_TELEMETRY_DIR opted in). None keeps the
        # decode loop's hot path at ONE `is not None` test per chunk —
        # the zero-overhead contract the serving latch test pins.
        self.telemetry = None

        # Device state: batched (or pooled paged) cache, per-slot
        # position, last token.
        dummy = jnp.zeros((self.n_slots, 1), jnp.int32)
        init_kw = {}
        if self.page_size:
            init_kw["block_tables"] = jnp.zeros(
                (self.n_slots, self._max_pages), jnp.int32)
            # host-side allocator: page 0 reserved as the junk dump
            self._free_pages = list(range(1, self.cfg.n_pages))
            self._tables = np.zeros(
                (self.n_slots, self._max_pages), np.int32)
            self._slot_pages = [[] for _ in range(self.n_slots)]
        # Only the cache's SHAPES come from init: every cache variable
        # starts as zeros, and a real init would also draw the whole
        # float32 parameter tree on the device beside the live params —
        # at published widths that alone overruns the chip.
        shapes = jax.eval_shape(
            functools.partial(self._model.init, **init_kw),
            jax.random.PRNGKey(0), dummy,
            positions=jnp.zeros((self.n_slots, 1), jnp.int32))
        self._cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes["cache"])
        # Categorized accounting (ISSUE 18): the KV cache/pool and the
        # serving params are long-lived trees — register them so the
        # mem sampler's category table attributes them instead of
        # lumping them into 'unattributed'. No-ops with telemetry off.
        from sparkdl_tpu.observe import mem as _mem_acct

        _mem_acct.register_tree(
            "kv_pages", lambda: _mem_acct.tree_nbytes(self._cache))
        _mem_acct.register_tree("params", params)
        self._pos = jnp.zeros((self.n_slots,), jnp.int32)
        self._token = jnp.zeros((self.n_slots,), jnp.int32)
        self._adapter_ids = np.zeros((self.n_slots,), np.int32)
        self.mesh = mesh
        self.params = params
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from sparkdl_tpu.parallel.sharding import (
                TRANSFORMER_RULES,
                param_sharding,
            )

            axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            missing = {"model", "fsdp"} - set(axis_sizes)
            if missing:
                raise ValueError(
                    f"TP serving needs mesh axes 'model' and 'fsdp' "
                    f"(missing {sorted(missing)}); build the mesh with "
                    "sparkdl_tpu.parallel.mesh.make_mesh"
                )
            self.params = jax.device_put(
                params,
                param_sharding(
                    params,
                    rules if rules is not None else TRANSFORMER_RULES,
                    mesh,
                ),
            )
            model_size = axis_sizes["model"]

            def cache_spec(leaf):
                # (n_slots, max_len, kv_heads, head_dim): kv heads ride
                # the TP axis alongside the head-sharded projections
                if leaf.ndim == 4 and leaf.shape[2] % model_size == 0:
                    return NamedSharding(mesh, P(None, None, "model"))
                return NamedSharding(mesh, P())

            self._cache = jax.device_put(
                self._cache, jax.tree.map(cache_spec, self._cache))
            rep = NamedSharding(mesh, P())
            self._pos = jax.device_put(self._pos, rep)
            self._token = jax.device_put(self._token, rep)
            self._rng = jax.device_put(self._rng, rep)

    # -- public API ---------------------------------------------------

    @property
    def _programs(self):
        return _engine_programs(self.cfg, self.temperature,
                                self._paged_sharded_mesh,
                                self.top_k, self.top_p)

    @property
    def _prefill_fn(self):
        return self._programs[0]

    @property
    def _suffix_prefill_fn(self):
        return self._programs[1]

    @property
    def _paged_prefill_fn(self):
        return self._programs[2]

    @property
    def _insert_fn(self):
        return self._programs[3]

    @property
    def _decode_chunk_fn(self):
        return self._programs[4]

    @property
    def _copy_pages_fn(self):
        return self._programs[5]

    def _decode_chunk_call(self, active, n):
        """``(args, kwargs)`` of one decode-program call over this
        engine's state: what the decode loop runs and what
        :meth:`lower_decode_chunk` shows are built here, once."""
        return (
            (self.params, self._cache, self._token, self._pos,
             jnp.asarray(active), self._rng, n),
            dict(
                # non-active rows masked to the dump page: a
                # mid-prefill slot's junk writes must not corrupt the
                # rows it has already prefilled
                tables=(jnp.asarray(
                    np.where(active[:, None], self._tables, 0))
                        if self.page_size else None),
                adapter_ids=(jnp.asarray(self._adapter_ids)
                             if self.cfg.multi_lora else None),
            ))

    def lower_decode_chunk(self, n=None):
        """The ``jax.stages.Lowered`` of this engine's decode program
        (``n`` tokens a chunk, default ``chunk``, every slot active)
        over its own state — for a caller that has to SEE which path
        the engine compiled: a pallas kernel shows in its text as
        ``tpu_custom_call``."""
        args, kwargs = self._decode_chunk_call(
            np.ones((self.n_slots,), bool), int(n or self.chunk))
        return self._decode_chunk_fn.lower(*args, **kwargs)

    def _adapter_arg(self, adapter_id):
        """adapter_ids argument for a batch-1 program call — None on
        single-adapter engines (keeps program signatures identical)."""
        if not self.cfg.multi_lora:
            return None
        return jnp.asarray([adapter_id], jnp.int32)

    def register_prefix(self, prefix_tokens, adapter_id=0):
        """Prefill a shared prompt PREFIX (a system prompt) once and
        cache its K/V rows; requests submitted with the returned
        ``prefix_id`` prefill only their suffix — admission cost drops
        from O(full prompt) to O(suffix) compute plus a device-side
        row copy. The cached rows are ADAPTER-SPECIFIC when the engine
        serves multi-LoRA (k/v projections carry the adapter), so a
        prefix is bound to ``adapter_id`` and only same-adapter
        requests may use it."""
        if self.cfg.multi_lora:
            if not 0 <= adapter_id < self.cfg.multi_lora:
                raise ValueError(
                    f"adapter_id {adapter_id} outside the stacked "
                    f"range [0, {self.cfg.multi_lora})"
                )
        elif adapter_id:
            raise ValueError(
                "adapter_id requires a multi_lora model "
                "(LlamaConfig.multi_lora > 0)"
            )
        prefix = np.asarray(prefix_tokens, np.int32).reshape(-1)
        if not len(prefix):
            raise ValueError("empty prefix")
        # < (not <=): a prefix filling the whole cache leaves no room
        # for even a one-token suffix, so it could never be used
        if len(prefix) >= self.cfg.max_cache_len:
            raise ValueError(
                f"prefix ({len(prefix)}) must be shorter than "
                f"max_cache_len ({self.cfg.max_cache_len})"
            )
        p_len = len(prefix)
        self._rng, sub = jax.random.split(self._rng)
        if self.page_size:
            # paged sharing: prefill the prefix ONCE into pool pages
            # that every consumer's block table will reference
            # read-only (the partial boundary page gets copied per
            # slot at admission — suffix writes land in it)
            need = -(-p_len // self.page_size)
            if need > len(self._free_pages):
                raise RuntimeError(
                    f"paged pool exhausted registering prefix: needs "
                    f"{need} pages, {len(self._free_pages)} free"
                )
            pages = [self._free_pages.pop() for _ in range(need)]
            table = np.zeros((1, self._max_pages), np.int32)
            table[0, :need] = pages
            padded = _pad_bucket(prefix, self.cfg.max_cache_len)
            self._cache, _tok, _lp = self._paged_prefill_fn(
                self.params, self._cache, jnp.asarray(padded),
                jnp.asarray(table), sub,
                jnp.asarray(p_len, jnp.int32), jnp.asarray(0, jnp.int32),
                adapter_ids=self._adapter_arg(adapter_id),
            )
            pid = f"prefix-{len(self._prefixes)}"
            self._prefixes[pid] = (prefix, pages, adapter_id)
            return pid
        padded = _pad_bucket(prefix, self.cfg.max_cache_len)
        cache, _, _ = self._prefill_fn(
            self.params, jnp.asarray(padded), sub, p_len,
            adapter_ids=self._adapter_arg(adapter_id),
        )
        # pin the shared index to the TRUE length (the bucket-padded
        # prefill advanced it to the bucket; junk rows beyond p_len
        # stay invisible and get overwritten by the suffix)
        cache = jax.tree.map(
            lambda x: jnp.full(x.shape, p_len, x.dtype)
            if x.ndim == 0 else x, cache)
        pid = f"prefix-{len(self._prefixes)}"
        self._prefixes[pid] = (prefix, cache, adapter_id)
        return pid

    def submit(self, prompt_tokens, max_new_tokens, prefix_id=None,
               adapter_id=0, stop=None):
        """Queue a request; returns its id. ``prefix_id`` (from
        :meth:`register_prefix`): the prompt must START with that
        prefix and extend it by at least one token. ``adapter_id``
        selects this request's LoRA adapter when the engine serves a
        multi-adapter tree (cfg.multi_lora). ``stop``: token-id
        sequences that end THIS request's generation when they appear
        (the stop sequence is included in the output, like eos);
        finish causes land in :attr:`finish_reasons` after run()."""
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if self.cfg.multi_lora:
            if not 0 <= adapter_id < self.cfg.multi_lora:
                raise ValueError(
                    f"adapter_id {adapter_id} outside the stacked "
                    f"range [0, {self.cfg.multi_lora})"
                )
        elif adapter_id:
            raise ValueError(
                "adapter_id requires a multi_lora model "
                "(LlamaConfig.multi_lora > 0)"
            )
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if len(prompt) + max_new_tokens > self.cfg.max_cache_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_cache_len "
                f"({self.cfg.max_cache_len})"
            )
        if prefix_id is not None:
            if prefix_id not in self._prefixes:
                raise ValueError(
                    f"unknown prefix_id {prefix_id!r}; call "
                    "register_prefix first"
                )
            prefix, _, pfx_adapter = self._prefixes[prefix_id]
            if self.cfg.multi_lora and pfx_adapter != adapter_id:
                raise ValueError(
                    f"prefix {prefix_id} is bound to adapter "
                    f"{pfx_adapter}; request uses {adapter_id} — "
                    "cached K/V rows are adapter-specific"
                )
            if (len(prompt) <= len(prefix)
                    or not np.array_equal(prompt[:len(prefix)], prefix)):
                raise ValueError(
                    f"prompt must extend the registered prefix "
                    f"{prefix_id} by at least one token"
                )
        rid = self._next_id
        self._next_id += 1
        if stop:
            seqs = tuple(
                tuple(int(t) for t in np.asarray(s).reshape(-1))
                for s in stop)
            if any(not s for s in seqs):
                raise ValueError("empty stop sequence")
            self._stops[rid] = seqs
        self._queue.append(
            (rid, prompt, int(max_new_tokens), prefix_id,
             int(adapter_id)))
        return rid

    def _try_admit_paged(self, slot_idx):
        """Paged admission: allocate the request's worst-case pages
        (whole prompt + budget) from the pool, point the slot's block
        table at them, prefill straight into the physical pages. With
        a prefix_id, the prefix's FULL pages are shared read-only
        across slots (only the partial boundary page is copied) and
        only the suffix is prefilled. Returns False (request left at
        the queue head) when the pool can't cover it yet — capacity
        admission control."""
        rid, prompt, max_new, prefix_id, adapter_id = self._queue[0]
        P = self.page_size
        p_len = len(prompt)
        total_pages = -(-self._worst_case_tokens(p_len, max_new) // P)
        # no-prefix admission = the empty-prefix special case: zero
        # shared pages, zero-length start, the whole prompt as suffix
        prefix = np.zeros((0,), np.int32)
        prefix_pages = []
        if prefix_id is not None:
            prefix, prefix_pages, _pfx_adapter = self._prefixes[prefix_id]
        n_full = len(prefix) // P
        shared = prefix_pages[:n_full]
        need = total_pages - len(shared)
        if need > len(self._free_pages):
            return False
        self._queue.pop(0)
        if self.telemetry is not None:
            # queue wait ends HERE — the engine is about to spend
            # prefill compute on this request
            self.telemetry.request_admitted(rid)
            # per-request worst-case KV footprint (ISSUE 18) — the
            # getattr guard keeps older three-hook telemetry adapters
            # (tests stub them) working unchanged
            hook = getattr(self.telemetry, "request_pages", None)
            if hook is not None:
                hook(rid, total_pages)
        own = [self._free_pages.pop() for _ in range(need)]
        self._slot_pages[slot_idx] = own
        self._tables[slot_idx] = 0
        self._tables[slot_idx, :total_pages] = shared + own

        # copy the partial boundary page (suffix writes land in it);
        # full shared pages are referenced, never written
        if len(prefix) % P:
            self._cache = self._copy_pages_fn(
                self._cache,
                jnp.asarray([prefix_pages[n_full]]),
                jnp.asarray([own[0]]),
            )
        suffix = prompt[len(prefix):]
        start = len(prefix)
        if len(prefix):
            self.stats["prefill_tokens_saved"] = (
                self.stats.get("prefill_tokens_saved", 0) + len(prefix))
        if self.prefill_chunk and len(suffix) > self.prefill_chunk:
            # Chunked prefill: this admission only STAGES the slot —
            # segments run one per engine-loop iteration, interleaved
            # with decode chunks, so a long prompt can't stall running
            # streams for its whole length. The slot stays inactive
            # (masked out of decode tables) until the final segment.
            self._prefilling[slot_idx] = {
                "rid": rid, "suffix": suffix, "start": start,
                "done": 0, "max_new": max_new,
                "adapter_id": adapter_id,
            }
            # first segment runs in the run-loop's advance phase — a
            # staging-time segment would make admission a TWO-segment
            # decode stall, breaking the one-per-iteration bound
            return True
        self._prefill_segment(slot_idx, suffix, start, len(suffix),
                              adapter_id, final=True,
                              rid=rid, max_new=max_new)
        return True

    def _prefill_segment(self, slot_idx, seg_tokens, start, true_len,
                         adapter_id, *, final, rid=None, max_new=None):
        """Run one paged prefill program over ``seg_tokens`` at logical
        offset ``start``. On the FINAL segment the sampled token (the
        request's first generated token) activates the slot."""
        self._rng, sub = jax.random.split(self._rng)
        # power-of-two pad with a floor of 8 (the global _bucket floor
        # of 32 would multiply the compute of small prefill_chunk
        # segments); the cache-end cap can't undercut true_len because
        # submit() bounds every position below max_cache_len
        b = 8
        while b < true_len:
            b *= 2
        bucket = min(b, self.cfg.max_cache_len - start)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :true_len] = seg_tokens[:true_len]
        self._cache, tok, lp = self._paged_prefill_fn(
            self.params, self._cache, jnp.asarray(padded),
            jnp.asarray(self._tables[slot_idx][None]), sub,
            jnp.asarray(true_len, jnp.int32),
            jnp.asarray(start, jnp.int32),
            adapter_ids=self._adapter_arg(adapter_id),
        )
        self.stats["prefill_segments"] = (
            self.stats.get("prefill_segments", 0) + 1)
        if final:
            p_len = start + true_len
            self._pos = self._pos.at[slot_idx].set(p_len)
            self._token = self._token.at[slot_idx].set(tok[0])
            self._adapter_ids[slot_idx] = adapter_id
            self._activate_slot(slot_idx, rid, max_new, tok, lp)

    def _advance_prefill(self, slot_idx):
        """One more segment for a mid-prefill slot; activates it on
        the last one."""
        st = self._prefilling[slot_idx]
        seg = min(self.prefill_chunk, len(st["suffix"]) - st["done"])
        final = st["done"] + seg == len(st["suffix"])
        self._prefill_segment(
            slot_idx, st["suffix"][st["done"]:st["done"] + seg],
            st["start"] + st["done"], seg, st["adapter_id"],
            final=final, rid=st["rid"], max_new=st["max_new"],
        )
        st["done"] += seg
        if final:
            del self._prefilling[slot_idx]

    def _pages_needed(self, req):
        """Fresh pages the queue-head request needs: its worst case
        minus the prefix pages it would SHARE (run()'s dead-end check
        must agree with _try_admit_paged or it cries exhaustion over
        requests that would admit)."""
        _, prompt, max_new, prefix_id, _aid = req
        total = -(-self._worst_case_tokens(len(prompt), max_new)
                  // self.page_size)
        if prefix_id is not None:
            prefix, _, _pfx = self._prefixes[prefix_id]
            total -= len(prefix) // self.page_size
        return total

    def _worst_case_tokens(self, p_len, max_new):
        """Cache rows a request can ever touch — page reservation AND
        the pool dead-end check size worst cases with this ONE hook
        (the speculative engine adds its k-token verify scratch)."""
        return p_len + max_new

    def _activate_slot(self, slot_idx, rid, max_new, tok, lp):
        """Shared admission epilogue: slot bookkeeping + the
        instant-finish check (first token is eos, or a one-token
        budget) — ONE definition for both admission paths."""
        s = self._slots[slot_idx]
        s.req_id, s.active = rid, True
        s.remaining = max_new - 1  # the prefill emitted token #1
        s.tokens = [int(np.asarray(tok)[0])]
        s.logprobs = [float(np.asarray(lp)[0])]
        if self._on_token is not None:
            self._on_token(rid, s.tokens[0])
        if self.eos_id is not None and s.tokens[0] == self.eos_id:
            self._finish(slot_idx, "eos")
        elif _hits_stop(s.tokens, self._stops.get(rid, ())):
            self._finish(slot_idx, "stop")
        elif s.remaining == 0:
            self._finish(slot_idx, "length")

    def _admit(self, slot_idx):
        rid, prompt, max_new, prefix_id, adapter_id = self._queue.pop(0)
        if self.telemetry is not None:
            self.telemetry.request_admitted(rid)
        p_len = len(prompt)
        self._rng, sub = jax.random.split(self._rng)
        if prefix_id is not None:
            prefix, prefix_cache, _pfx_adapter = self._prefixes[prefix_id]
            suffix = prompt[len(prefix):]
            padded = _pad_bucket(
                suffix, self.cfg.max_cache_len - len(prefix))
            one_cache, tok, lp = self._suffix_prefill_fn(
                self.params, prefix_cache, jnp.asarray(padded), sub,
                len(suffix),
                adapter_ids=self._adapter_arg(adapter_id),
            )
            self.stats["prefill_tokens_saved"] = (
                self.stats.get("prefill_tokens_saved", 0) + len(prefix))
        else:
            padded = _pad_bucket(prompt, self.cfg.max_cache_len)
            one_cache, tok, lp = self._prefill_fn(
                self.params, jnp.asarray(padded), sub, p_len,
                adapter_ids=self._adapter_arg(adapter_id),
            )
        self._cache, self._pos, self._token = self._insert_fn(
            self._cache, self._pos, self._token, one_cache, tok,
            p_len, slot_idx,
        )
        self._adapter_ids[slot_idx] = adapter_id
        self._activate_slot(slot_idx, rid, max_new, tok, lp)

    def _finish(self, slot_idx, reason="length"):
        s = self._slots[slot_idx]
        self._results[s.req_id] = np.asarray(s.tokens, np.int32)
        self._finish_reasons[s.req_id] = reason
        self._logprobs[s.req_id] = np.asarray(s.logprobs, np.float32)
        self._stops.pop(s.req_id, None)
        s.active = False
        s.tokens = []
        s.logprobs = []
        if self.page_size:
            self._free_pages.extend(self._slot_pages[slot_idx])
            self._slot_pages[slot_idx] = []
            self._tables[slot_idx] = 0

    def run(self, progress=None, on_token=None):
        """Drain the queue; returns {req_id: generated tokens}.

        Each ``run()`` returns only the requests finished during THIS
        drain — completed results are handed to the caller and cleared,
        so a reused engine neither replays old bursts nor grows its
        result map without bound.

        ``on_token(req_id, token)``: streaming callback invoked for
        every accepted token in generation order (a serving front-end
        pushes these to clients; delivery granularity is the decode
        chunk — the XLA-first trade-off documented on the class).
        ``progress(engine)``: coarse per-iteration hook."""
        self._on_token = on_token
        try:
            return self._run(progress)
        finally:
            # never retain the caller's closure (and whatever client
            # buffers/connections it holds) past this run
            self._on_token = None

    def _run(self, progress):
        while (self._queue or self._prefilling
               or any(s.active for s in self._slots)):
            # fill free slots from the queue (paged: only while the
            # pool covers the next request's worst case)
            active = self._fill_slots()
            if not active.any():
                self._deadend_check()
                continue
            # Chunk length: sized to the soonest-finishing active slot
            # (so its replacement isn't kept waiting), then rounded UP
            # to a power of two — the scan program compiles O(log
            # chunk) times total instead of once per distinct tail
            # length. Overshoot is discarded host-side (same as
            # mid-chunk eos); decode_chunk clamps the position advance
            # at max_cache_len-1 so overshot steps of a budget-exhausted
            # slot can never write past the cache.
            need = min(s.remaining for s in self._slots if s.active)
            n = 1
            while n < need and n < self.chunk:
                n *= 2
            n = min(n, self.chunk)
            args, kwargs = self._decode_chunk_call(active, n)
            (self._cache, self._token, self._pos, self._rng,
             toks, lps) = self._decode_chunk_fn(*args, **kwargs)
            toks = np.asarray(toks)                 # (n, n_slots)
            lps = np.asarray(lps)
            self.stats["steps"] += n
            self.stats["total_slot_steps"] += n * self.n_slots
            self.stats["active_slot_steps"] += int(active.sum()) * n
            self._observe_chunk(int(active.sum()), n)
            for i, s in enumerate(self._slots):
                if s.active:
                    self._accept_tokens(i, toks[:, i], lps[:, i])
            if progress is not None:
                progress(self)
        return self._drain_results()

    def _fill_slots(self):
        """Admit queued requests into free slots (paged: only while the
        pool covers worst cases), advance any staged chunked prefills,
        and return the active mask. Shared by both decode loops."""
        for i, s in enumerate(self._slots):
            if (not s.active and i not in self._prefilling
                    and self._queue):
                if self.page_size:
                    if not self._try_admit_paged(i):
                        if self.telemetry is not None:
                            # requeued, not refused: the pool can't
                            # cover the head's worst case yet
                            self.telemetry.admission_deferred(
                                "pool_exhausted")
                        break
                else:
                    self._admit(i)
        # one prefill segment per staged slot per iteration:
        # long-prompt admission interleaves with decode instead of
        # stalling it for the whole prompt
        for i in list(self._prefilling):
            self._advance_prefill(i)
        return np.array([s.active for s in self._slots])

    def _observe_chunk(self, active_count, n_tokens):
        """Telemetry for one decode chunk (or speculation round) —
        ONE definition for both decode loops, so utilization metrics
        can never skew between the plain and speculative engines."""
        if self.telemetry is not None:
            self.telemetry.decode_chunk(
                active_count, self.n_slots, n_tokens,
                free_pages=(len(self._free_pages)
                            if self.page_size else None),
                n_pages=(self.cfg.n_pages if self.page_size else None),
            )

    def _deadend_check(self):
        """Nothing active: raise when the queue head can NEVER admit
        (genuine pool shortfall) rather than spinning forever — an
        instantly-finished admission (eos / one-token budget) also
        lands here, with pages free again, and is not a dead end."""
        if self._queue and self.page_size and not self._prefilling:
            need = self._pages_needed(self._queue[0])
            if need > len(self._free_pages):
                err = RuntimeError(
                    f"paged pool exhausted: request needs "
                    f"{need} fresh pages, pool has "
                    f"{len(self._free_pages)} free and nothing "
                    "left to drain — raise n_pages"
                )
                # Engine-admission OOM forensics (ISSUE 18): the pool
                # shortfall is the serving tier's allocation failure —
                # write the report before the engine thread unwinds.
                # Inert without SPARKDL_TPU_TELEMETRY_DIR.
                from sparkdl_tpu.observe import mem

                mem.write_oom_report(
                    "admission", err,
                    extra={"pages_needed": need,
                           "pages_free": len(self._free_pages),
                           "n_pages": self.cfg.n_pages,
                           "page_size": self.page_size})
                raise err

    def _accept_tokens(self, slot_idx, tokens, logprobs):
        """Append generated tokens to a slot (streaming callback, eos
        and budget enforcement). Returns True when the slot finished —
        trailing tokens past eos/budget are discarded. ONE definition
        shared by the chunked and the speculative decode loops."""
        s = self._slots[slot_idx]
        stops = self._stops.get(s.req_id, ())
        for t, lp in zip(tokens, logprobs):
            s.tokens.append(int(t))
            s.logprobs.append(float(lp))
            s.remaining -= 1
            if self._on_token is not None:
                self._on_token(s.req_id, int(t))
            if self.eos_id is not None and int(t) == self.eos_id:
                self._finish(slot_idx, "eos")
                return True
            if stops and _hits_stop(s.tokens, stops):
                self._finish(slot_idx, "stop")
                return True
            if s.remaining == 0:
                self._finish(slot_idx, "length")
                return True
        return False

    def abort_requests(self):
        """Discard every queued and active request WITHOUT producing
        results — service fault recovery (models/server.py): after a
        run() fault the engine may hold a poison request queued or
        mid-slot, and re-running it would re-fire the fault forever.
        Frees paged pool pages and deactivates slots; abandoned cache
        rows are junk that later admissions overwrite (the same
        invariant slot reuse already relies on)."""
        self._queue.clear()
        self._prefilling.clear()
        self._stops.clear()
        self._finish_reasons.clear()
        self._logprobs.clear()
        self._results.clear()
        for i, s in enumerate(self._slots):
            if self.page_size and self._slot_pages[i]:
                self._free_pages.extend(self._slot_pages[i])
                self._slot_pages[i] = []
                self._tables[i] = 0
            s.active = False
            s.req_id = -1
            s.remaining = 0
            s.tokens = []
            s.logprobs = []

    def _drain_results(self):
        """Final stats + hand the burst's results to the caller;
        per-request finish causes land in :attr:`finish_reasons`."""
        self.stats["utilization"] = (
            self.stats["active_slot_steps"]
            / max(1, self.stats["total_slot_steps"])
        )
        self.finish_reasons = self._finish_reasons
        self._finish_reasons = {}
        self.logprobs = self._logprobs
        self._logprobs = {}
        out = self._results
        self._results = {}
        return out


# ---------------------------------------------------------------------------
# Speculative continuous batching: the engine's slot scheduler composed
# with draft-propose / target-verify rounds (models/speculative.py has
# the single-burst lockstep version; production stacks run speculation
# INSIDE the batching engine, per-slot).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _spec_engine_programs(dec_cfg, draft_cfg, k, temperature, top_k=0,
                          top_p=1.0):
    """(draft_prefill, draft_insert, draft_suffix_prefill,
    spec_round) — jitted once per (target config, draft config, k,
    temperature, top_k, top_p). temperature == 0:
    greedy longest-agreeing-prefix acceptance (token-exact vs plain
    greedy decode). temperature > 0: distribution-exact rejection
    sampling (models/speculative.spec_sample_tokens) — marginals equal
    target-only sampling, the draft moves only throughput."""
    from sparkdl_tpu.models.generate import restrict_logits
    from sparkdl_tpu.models.llama import Llama
    from sparkdl_tpu.models.speculative import spec_sample_tokens

    target = Llama(dec_cfg)
    draft = Llama(draft_cfg)

    def _restricted_probs(logits):
        # the rejection scheme is exact for whatever target
        # distribution it is fed: restricting BOTH p and q to the
        # top-k/nucleus support makes the output distribution equal
        # restricted-target-only sampling (vLLM's composition)
        return jax.nn.softmax(
            restrict_logits(logits / temperature, top_k=top_k,
                            top_p=top_p),
            axis=-1,
        )

    @jax.jit
    def draft_prefill(d_params, padded_prompt):
        """Prompt through the DRAFT (logits discarded): its slot cache
        only has to hold the prompt's K/V — junk pad rows beyond the
        true length stay invisible under the position mask."""
        _, st = draft.apply(
            {"params": d_params}, padded_prompt, mutable=["cache"])
        return st["cache"]

    @jax.jit
    def draft_insert(d_cache, one_cache, slot):
        return jax.tree.map(
            lambda full, one: (
                full if full.ndim == 0 else full.at[slot].set(one[0])
            ),
            d_cache, one_cache,
        )

    @jax.jit
    def draft_suffix_prefill(d_params, prefix_cache, padded_suffix):
        """Continue a stored DRAFT prefix cache over a request's
        suffix (logits discarded) — the draft-side twin of the
        engine's suffix_prefill."""
        _, st = draft.apply(
            {"params": d_params, "cache": prefix_cache}, padded_suffix,
            mutable=["cache"],
        )
        return st["cache"]

    @functools.partial(jax.jit, donate_argnums=(1, 3))
    def spec_round(params, cache, d_params, d_cache, token, pos,
                   active, rng, tables=None):
        """One speculation round over every slot: the draft scans k
        slot-mapped steps, then ONE target forward scores the k+1
        positions, and acceptance runs IN-GRAPH — the host reads back
        only (tokens, counts). Rejected rows above each slot's
        accepted position are junk that the NEXT round's writes cover
        before any query can see them (write window [pos', pos'+k]
        always spans the previous round's junk because pos advances
        by at most k+1)."""
        L = dec_cfg.max_cache_len
        rng, d_rng = jax.random.split(rng)

        def body(carry, step_rng):
            d_cache, tok, p = carry
            logits, st = draft.apply(
                {"params": d_params, "cache": d_cache}, tok[:, None],
                positions=p[:, None], mutable=["cache"],
            )
            last = logits[:, -1]
            if temperature == 0.0:
                nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
                q_row = jnp.zeros_like(last)  # unused in greedy
            else:
                q_row = _restricted_probs(last)
                nxt = jax.random.categorical(
                    step_rng, jnp.log(jnp.maximum(q_row, 1e-30)),
                    axis=-1,
                ).astype(jnp.int32)
            p = jnp.where(active, jnp.minimum(p + 1, L - 1), p)
            return (st["cache"], nxt, p), (nxt, q_row)

        (d_cache, last_tok, last_p), (prop, q_probs) = jax.lax.scan(
            body, (d_cache, token, pos), jax.random.split(d_rng, k))
        # one extra logits-discarded step writes the LAST proposal's
        # K/V row: a fully-accepted round advances past it, and
        # without this write the draft's next round attends a junk
        # row — acceptance collapses (exactness is unaffected; the
        # verify is authoritative). Same trick as
        # speculative_generate's propose.
        _, st = draft.apply(
            {"params": d_params, "cache": d_cache}, last_tok[:, None],
            positions=last_p[:, None], mutable=["cache"],
        )
        d_cache = st["cache"]
        prop = prop.T                                     # (b, k)

        offs = jnp.arange(k + 1)
        ppos = jnp.minimum(pos[:, None] + offs[None, :], L - 1)
        ppos = jnp.where(active[:, None], ppos, pos[:, None])
        seq = jnp.concatenate([token[:, None], prop], axis=1)
        logits, st = target.apply(
            {"params": params, "cache": cache}, seq, positions=ppos,
            block_tables=tables, mutable=["cache"],
        )
        if temperature == 0.0:
            from sparkdl_tpu.models.speculative import assemble_round

            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            agree = prop == greedy[:, :k]
            all_acc = agree.all(-1)
            m = jnp.where(all_acc, k, jnp.argmin(agree, -1))
            final = jnp.take_along_axis(
                greedy, m[:, None], axis=1)[:, 0]
            tokens, counts = assemble_round(prop, m, final)
            lp_all = jax.nn.log_softmax(
                logits.astype(jnp.float32), axis=-1)
        else:
            rng, s_rng = jax.random.split(rng)
            p_probs = _restricted_probs(logits)
            tokens, counts = spec_sample_tokens(
                q_probs.transpose(1, 0, 2), p_probs, prop, s_rng)
            lp_all = jnp.log(jnp.maximum(p_probs, 1e-30))
        # chosen-token logprob under the TARGET distribution at each
        # verified position (the same convention as _sample_lp)
        lps = jnp.take_along_axis(
            lp_all, tokens[..., None], axis=-1)[..., 0]   # (b, k+1)
        return st["cache"], d_cache, tokens, counts, lps, rng

    return draft_prefill, draft_insert, draft_suffix_prefill, spec_round


class SpeculativeBatchingEngine(ContinuousBatchingEngine):
    """Continuous batching with per-slot speculative decoding: an int8
    (or any same-interface) DRAFT proposes ``k`` tokens per slot, one
    target forward verifies all slots, and each slot independently
    accepts its longest agreeing prefix plus the target's bonus token
    — greedy outputs are EXACTLY the plain engine's (speculative
    identity per slot; no lockstep barrier like
    :func:`speculative_generate`'s whole-batch agree).

    ``temperature > 0`` switches the round to distribution-exact
    rejection sampling (:func:`~sparkdl_tpu.models.speculative.
    spec_sample_tokens`): accept proposal x with prob min(1, p(x)/q(x)),
    resample the first rejection from the residual (p-q)+ — marginals
    equal target-only sampling; the draft moves only throughput.

    The TARGET cache may be paged (``page_size=``): verify writes ride
    the slot's block table, and page reservation adds the k-token
    scratch via :meth:`_worst_case_tokens`. The DRAFT always keeps a
    dense slot cache — proposals are the draft's problem, and a dense
    (typically int8) draft cache is simpler than a second page pool.

    Prefix caching works on both sides: the target through the base
    engine's dense-copy / shared-pool-pages machinery, the draft
    through its own dense prefix caches — prefixed admissions prefill
    only the suffix on both models.

    Out of scope (raises): multi-adapter, chunked prefill, TP mesh.
    """

    def __init__(self, model, params, draft_params, *, n_slots=4,
                 eos_id=None, k=4, rng=None, draft_model=None,
                 temperature=0.0, page_size=0, n_pages=None,
                 top_k=0, top_p=1.0):
        cfg = model.cfg
        if cfg.multi_lora:
            raise ValueError(
                "SpeculativeBatchingEngine is single-adapter only")
        # set before super(): _worst_case_tokens (k-dependent) is live
        # as soon as the base class can admit
        self.k = int(k)
        if self.k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        super().__init__(model, params, n_slots=n_slots,
                         temperature=temperature, eos_id=eos_id,
                         rng=rng, page_size=page_size, n_pages=n_pages,
                         top_k=top_k, top_p=top_p)
        d_base = draft_model.cfg if draft_model is not None else cfg
        self._draft_cfg = dataclasses.replace(
            d_base, decode=True, max_cache_len=self.cfg.max_cache_len,
            page_size=0, n_pages=0,
        )
        self.draft_params = draft_params
        self._draft_prefixes = {}  # prefix_id -> draft dense cache
        from sparkdl_tpu.models.llama import Llama

        dummy = jnp.zeros((self.n_slots, 1), jnp.int32)
        self._d_cache = Llama(self._draft_cfg).init(
            jax.random.PRNGKey(1), dummy,
            positions=jnp.zeros((self.n_slots, 1), jnp.int32),
        )["cache"]
        self.stats.update(rounds=0, proposed=0, accepted=0)

    @property
    def _spec_programs(self):
        return _spec_engine_programs(self.cfg, self._draft_cfg, self.k,
                                     self.temperature, self.top_k,
                                     self.top_p)

    def _worst_case_tokens(self, p_len, max_new):
        # + k scratch: a verify may write k positions past the final
        # accepted token; those rows (and, paged, their pages) must be
        # the request's OWN scratch, never a neighbour's data.
        return p_len + max_new + self.k

    def submit(self, prompt_tokens, max_new_tokens, prefix_id=None,
               adapter_id=0, stop=None):
        prompt = np.asarray(prompt_tokens, np.int32).reshape(-1)
        if self._worst_case_tokens(len(prompt), max_new_tokens) \
                > self.cfg.max_cache_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) + k ({self.k}) speculation "
                f"scratch exceeds max_cache_len "
                f"({self.cfg.max_cache_len}); raise max_cache_len or "
                "lower k"
            )
        return super().submit(prompt, max_new_tokens,
                              prefix_id=prefix_id,
                              adapter_id=adapter_id, stop=stop)

    def register_prefix(self, prefix_tokens, adapter_id=0):
        """Shared-prefix caching for BOTH models: the target side goes
        through the base engine (dense cache copy or read-only shared
        pool pages); the draft keeps its own dense prefix cache, so a
        prefixed admission prefills only the suffix on both sides —
        and the draft stays position-correct, which speculation's
        acceptance rate depends on."""
        pid = super().register_prefix(prefix_tokens, adapter_id)
        draft_prefill = self._spec_programs[0]
        prefix = np.asarray(prefix_tokens, np.int32).reshape(-1)
        padded = _pad_bucket(prefix, self.cfg.max_cache_len)
        d_cache = draft_prefill(self.draft_params, jnp.asarray(padded))
        # pin the shared index to the TRUE length (the bucket-padded
        # prefill advanced it to the bucket) — mirrors the base
        # engine's dense prefix path
        d_cache = jax.tree.map(
            lambda x: jnp.full(x.shape, len(prefix), x.dtype)
            if x.ndim == 0 else x, d_cache)
        self._draft_prefixes[pid] = d_cache
        return pid

    def _draft_admit(self, slot_idx, prompt, prefix_id):
        """Prompt (or its suffix past a cached prefix) through the
        draft into its dense slot cache — shared epilogue of both
        admission paths."""
        if slot_idx in self._prefilling:
            # chunked prefill STAGES the slot inactive; the early
            # return below would then skip the draft prefill and this
            # request would speculate against the previous occupant's
            # draft K/V (silent acceptance collapse) — fail fast
            # instead. __init__ never enables prefill_chunk; this
            # guards future plumbing.
            raise RuntimeError(
                "speculative engine does not support chunked prefill"
            )
        if not self._slots[slot_idx].active:
            # instantly finished (first token was eos / 1-token
            # budget): the slot will be re-admitted fresh — don't pay
            # a draft prefill + full-tree insert for it
            return
        draft_prefill, draft_insert, draft_suffix_prefill = \
            self._spec_programs[:3]
        if prefix_id is not None:
            prefix, _, _aid = self._prefixes[prefix_id]
            padded = _pad_bucket(prompt[len(prefix):],
                                 self.cfg.max_cache_len - len(prefix))
            one = draft_suffix_prefill(
                self.draft_params, self._draft_prefixes[prefix_id],
                jnp.asarray(padded))
        else:
            padded = _pad_bucket(prompt, self.cfg.max_cache_len)
            one = draft_prefill(self.draft_params, jnp.asarray(padded))
        self._d_cache = draft_insert(self._d_cache, one, slot_idx)

    def _admit(self, slot_idx):
        # capture before super() pops the queue head
        _, prompt, _, prefix_id, _ = self._queue[0]
        super()._admit(slot_idx)
        self._draft_admit(slot_idx, prompt, prefix_id)

    def _try_admit_paged(self, slot_idx):
        _, prompt, _, prefix_id, _ = self._queue[0]
        if not super()._try_admit_paged(slot_idx):
            return False
        self._draft_admit(slot_idx, prompt, prefix_id)
        return True

    def _run(self, progress):
        spec_round = self._spec_programs[3]
        while (self._queue or self._prefilling
               or any(s.active for s in self._slots)):
            active = self._fill_slots()
            if not active.any():
                self._deadend_check()
                continue
            (self._cache, self._d_cache, tokens, counts, lps,
             self._rng) = spec_round(
                self.params, self._cache, self.draft_params,
                self._d_cache, self._token, self._pos,
                jnp.asarray(active), self._rng,
                tables=(jnp.asarray(
                    np.where(active[:, None], self._tables, 0))
                        if self.page_size else None),
            )
            tokens = np.asarray(tokens)               # (b, k+1)
            counts = np.asarray(counts)               # (b,)
            lps = np.asarray(lps)
            n_act = int(active.sum())
            self.stats["rounds"] += 1
            self.stats["proposed"] += self.k * n_act
            self.stats["steps"] += 1
            self.stats["total_slot_steps"] += self.n_slots
            self.stats["active_slot_steps"] += n_act
            # one speculation round = one "chunk" of up to k+1 tokens
            # per slot
            self._observe_chunk(n_act, self.k + 1)
            new_pos = np.asarray(self._pos).copy()
            new_tok = np.asarray(self._token).copy()
            for i, s in enumerate(self._slots):
                if not s.active:
                    continue
                cnt = int(counts[i])
                # cnt-1 proposals survived; the last token is the
                # bonus (full acceptance) or the corrected/resampled
                # one (first rejection)
                self.stats["accepted"] += cnt - 1
                if not self._accept_tokens(i, tokens[i, :cnt],
                                           lps[i, :cnt]):
                    new_pos[i] += cnt
                    new_tok[i] = tokens[i, cnt - 1]
            self._pos = jnp.asarray(new_pos)
            self._token = jnp.asarray(new_tok)
            if progress is not None:
                progress(self)
        self.stats["acceptance_rate"] = (
            self.stats["accepted"] / max(1, self.stats["proposed"])
        )
        return self._drain_results()
