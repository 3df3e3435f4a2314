"""Llama-family decoder (the flagship for the Llama-3-8B LoRA
north-star config in BASELINE.json), written TPU-first:

- bf16 activations/params with fp32 softmax and norms (MXU-native).
- module names chosen to match
  :data:`sparkdl_tpu.parallel.sharding.TRANSFORMER_RULES` so GSPMD
  tensor parallelism is a pure annotation change.
- attention is injectable: dense reference attention on one chip,
  :func:`sparkdl_tpu.parallel.ring_attention.ring_self_attention` when
  the sequence axis is sharded.
- static shapes everywhere; RoPE precomputed; GQA via head repetition.
"""

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.lora import LoRADense


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    # RoPE rescaling for long-context checkpoints: None,
    # ("linear", factor), or ("llama3", factor, low_freq_factor,
    # high_freq_factor, original_max_position_embeddings) — a TUPLE
    # (hashable: configs key jit/program caches). See rope_freqs.
    rope_scaling: Optional[tuple] = None
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "reference"  # "reference" (train) | "flash" (serve)
    # flash tile size; 0 = library default (SPARKDL_TPU_FLASH_BLOCK read
    # once at import, else chosen from the shape by
    # ops.pallas.flash_attention.flash_tiles). Part of the config so
    # sweeps retune the kernel through the jit cache key instead of a
    # trace-time env read.
    flash_block: int = 0
    decode: bool = False          # KV-cache autoregressive mode
    max_cache_len: int = 2048     # KV-cache capacity for decoding
    # Paged KV cache (serving): page_size > 0 replaces the per-row
    # dense cache with a POOLED physical cache of n_pages pages shared
    # by all batch rows via per-row block tables (vLLM-style, XLA
    # gather/scatter). Requires the slot-mapped decode path (explicit
    # positions) and block_tables passed to __call__.
    page_size: int = 0
    n_pages: int = 0
    # Paged decode attention kernel: "auto" = pallas kernel on TPU for
    # single-step decode (reads ONLY a row's own pages through the
    # block table; the XLA fallback gathers the whole logical view and
    # repeats K/V for GQA — ~3x the HBM traffic on a bandwidth-bound
    # step), "off" = always the gather path, "force_interpret" = run
    # the kernel interpreted off-TPU (tests). Under a TP mesh the
    # serving engine binds the kernel via shard_map over the kv-head
    # axis (paged_attention_decode_sharded) when the cache is
    # head-sharded, falling back to the gather path otherwise — a raw
    # pallas_call cannot be partitioned by GSPMD.
    paged_kernel: str = "auto"
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Sequence[str] = ("q_proj", "v_proj")
    quant: str = ""               # "" (dense) | "int8" | "int4" weight-only
                                  # serving (params from
                                  # models.quant.quantize_llama_params)
    # int4 group size (rows per scale). Must match the checkpoint's
    # quantize group: flax pins param shapes, so the scale tree's
    # (K//group, N) layout is part of the serving config, not a
    # runtime inference.
    quant_group: int = 64
    # Quant-matmul kernel mode for the int8/int4 GEMMs: "" defers to
    # the SPARKDL_TPU_KERNEL_QUANT_MATMUL knob (read once at import of
    # ops.pallas.quantized_matmul); "auto"/"off"/"force_interpret"
    # mirror paged_kernel's vocabulary and, being config, are part of
    # the jit cache key — the per-engine override tests and A/B
    # benches flip THIS, never the env mid-process.
    quant_kernel: str = ""
    # Multi-LoRA serving: > 0 stacks that many adapters on the frozen
    # base (params from models.lora.stack_lora_adapters); adapter_ids
    # passed to __call__ select one per batch row (S-LoRA-style
    # multi-tenant serving). Adapter targets must live in attention.
    multi_lora: int = 0
    # Sparse-FFN (Mixtral-style) decoder: n_experts > 0 replaces the
    # dense MLP with a top-k routed expert MLP on every moe_every-th
    # layer (1 = all layers). Router-balance aux loss: apply with
    # mutable=["intermediates"] + models.moe.moe_aux_loss.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 1

    def __post_init__(self):
        if self.paged_kernel not in ("auto", "off", "force_interpret"):
            # a typo'd value would silently behave like "auto" in the
            # dispatch (same lesson as make_ring_attention's impl check)
            raise ValueError(
                f"paged_kernel must be 'auto', 'off', or "
                f"'force_interpret', got {self.paged_kernel!r}"
            )
        if self.quant_kernel not in ("", "auto", "off",
                                     "force_interpret"):
            raise ValueError(
                f"quant_kernel must be '', 'auto', 'off', or "
                f"'force_interpret', got {self.quant_kernel!r}"
            )
        if self.multi_lora:
            attn_names = {"q_proj", "k_proj", "v_proj", "o_proj"}
            bad = set(self.lora_targets) - attn_names
            if bad:
                raise ValueError(
                    f"multi_lora supports attention adapter targets "
                    f"only; got {sorted(bad)}"
                )
            if self.quant:
                raise ValueError(
                    "multi_lora and quant are mutually exclusive "
                    "(quantize a merged single-adapter tree instead)"
                )
            if not self.lora_rank:
                raise ValueError("multi_lora requires lora_rank > 0")
        if self.n_experts > 0:
            if not 0 < self.moe_top_k <= self.n_experts:
                raise ValueError(
                    f"moe_top_k={self.moe_top_k} must be in "
                    f"[1, n_experts={self.n_experts}]"
                )
            if self.moe_every < 1:
                raise ValueError(
                    f"moe_every={self.moe_every} must be >= 1"
                )

    @classmethod
    def llama3_8b(cls, **kw):
        """Llama-3-8B's published shape; ``kw`` overrides any field,
        the ones set here included (``n_layers=4`` cuts depth)."""
        defaults = dict(vocab_size=128256, d_model=4096, n_layers=32,
                        n_heads=32, n_kv_heads=8, d_ff=14336)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def llama31_8b(cls, **kw):
        """Llama-3.1-8B: the 3.0 architecture + the official llama3
        RoPE rescale (factor 8 over the 8192-token original window)
        that buys the 128k context."""
        kw.setdefault("rope_scaling",
                      ("llama3", 8.0, 1.0, 4.0, 8192))
        return cls.llama3_8b(**kw)

    @classmethod
    def tiny(cls, **kw):
        """CI-size config (full architecture, small dims)."""
        defaults = dict(vocab_size=256, d_model=64, n_layers=2,
                        n_heads=4, n_kv_heads=2, d_ff=128)
        defaults.update(kw)
        return cls(**defaults)


def _dense(cfg, features, name):
    if cfg.quant not in ("", "int8", "int4"):
        raise ValueError(
            f"unknown quant mode {cfg.quant!r}; expected '', 'int8', "
            "or 'int4'"
        )
    if cfg.quant:
        # Serving mode: LoRA must be merged first (merge_lora_with) —
        # a bf16 adapter over a quantized base is not supported.
        if cfg.lora_rank:
            raise ValueError(
                f"quant={cfg.quant!r} requires lora_rank=0 (merge "
                "adapters with merge_lora_with, then quantize)"
            )
        from sparkdl_tpu.models.quant import QuantDense, QuantDense4

        if cfg.quant == "int4":
            return QuantDense4(features=features, dtype=cfg.dtype,
                               group=cfg.quant_group,
                               kernel=cfg.quant_kernel, name=name)
        return QuantDense(features=features, dtype=cfg.dtype,
                          kernel=cfg.quant_kernel, name=name)
    if cfg.lora_rank and name in cfg.lora_targets:
        return LoRADense(features=features, rank=cfg.lora_rank,
                         alpha=cfg.lora_alpha, dtype=cfg.dtype, name=name)
    return nn.Dense(features=features, use_bias=False, dtype=cfg.dtype,
                    name=name)


def _apply_dense(cfg, features, name, x, adapter_ids=None):
    """Apply the projection ``name``: per-row multi-adapter LoRA when
    cfg.multi_lora targets it (ids default to adapter 0 so paths that
    never select — training, plain generate — still work), else the
    standard dense/LoRA/quant module from :func:`_dense`."""
    if cfg.multi_lora and cfg.lora_rank and name in cfg.lora_targets:
        from sparkdl_tpu.models.lora import MultiLoRADense

        if adapter_ids is None:
            adapter_ids = jnp.zeros((x.shape[0],), jnp.int32)
        return MultiLoRADense(
            features=features, rank=cfg.lora_rank, alpha=cfg.lora_alpha,
            n_adapters=cfg.multi_lora, dtype=cfg.dtype, name=name,
        )(x, jnp.asarray(adapter_ids, jnp.int32))
    return _dense(cfg, features, name)(x)


def rope_freqs(head_dim, max_seq, theta, scaling=None):
    """RoPE cos/sin tables. ``scaling`` (LlamaConfig.rope_scaling):
    None, ``("linear", factor)`` — positions stretched uniformly — or
    ``("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_len)`` — Llama-3.1's per-frequency remap: wavelengths
    short relative to the ORIGINAL training context keep full
    resolution, long wavelengths stretch by ``factor``, the band
    between interpolates smoothly (matches HF's
    _compute_llama3_parameters, pinned by the conversion parity
    tests)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    if scaling is not None:
        kind = scaling[0]
        if kind == "linear":
            inv = inv / scaling[1]
        elif kind == "llama3":
            _, factor, low_ff, high_ff, orig_len = scaling
            wavelen = 2.0 * jnp.pi / inv
            low_wl = orig_len / low_ff
            high_wl = orig_len / high_ff
            smooth = (orig_len / wavelen - low_ff) / (high_ff - low_ff)
            inv_mid = (1 - smooth) * inv / factor + smooth * inv
            inv = jnp.where(
                wavelen < high_wl, inv,
                jnp.where(wavelen > low_wl, inv / factor, inv_mid))
        else:
            raise ValueError(f"unknown rope scaling kind {kind!r}")
    t = jnp.arange(max_seq, dtype=jnp.float32)
    ang = jnp.outer(t, inv)                       # (S, D/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin, positions):
    # x: (B, S, H, D); positions: (S,) or (B, S)
    c = cos[positions][..., None, :]              # (.., S, 1, D/2)
    s = sin[positions][..., None, :]
    if c.ndim == 3:                               # positions was (S,)
        c, s = c[None], s[None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (norm * scale).astype(x.dtype)


class Attention(nn.Module):
    cfg: LlamaConfig
    attention_fn: Optional[Callable] = None
    # mesh-bound paged decode kernel (TP serving): the engine injects
    # ops.pallas.paged_attention.paged_attention_decode_sharded here —
    # takes priority over cfg.paged_kernel's single-device dispatch
    paged_attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, cos, sin, positions, block_tables=None,
                 adapter_ids=None):
        cfg = self.cfg
        head_dim = cfg.d_model // cfg.n_heads
        b, s, _ = x.shape
        q = _apply_dense(cfg, cfg.n_heads * head_dim, "q_proj", x,
                         adapter_ids)
        k = _apply_dense(cfg, cfg.n_kv_heads * head_dim, "k_proj", x,
                         adapter_ids)
        v = _apply_dense(cfg, cfg.n_kv_heads * head_dim, "v_proj", x,
                         adapter_ids)
        q = q.reshape(b, s, cfg.n_heads, head_dim)
        k = k.reshape(b, s, cfg.n_kv_heads, head_dim)
        v = v.reshape(b, s, cfg.n_kv_heads, head_dim)

        # Autoregressive decoding (cfg.decode): a 'cache' collection
        # holds rotated K/V for past positions; each call appends the
        # current step and attends over the visible prefix. Positions
        # are derived from the cache index — the single source of
        # truth — so RoPE and the mask can never disagree.
        if cfg.decode and cfg.page_size:
            # PAGED cache: one pooled physical (n_pages, page, kvh, hd)
            # store shared by all rows; a row's logical positions map
            # through its block table to (page, offset). Slot-mapped
            # only: the caller owns positions AND block tables.
            if positions is None or block_tables is None:
                raise ValueError(
                    "paged decode needs explicit positions and "
                    "block_tables (the serving engine provides both)"
                )
            P = cfg.page_size
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros(
                    (cfg.n_pages, P, cfg.n_kv_heads, head_dim), k.dtype),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(
                    (cfg.n_pages, P, cfg.n_kv_heads, head_dim), v.dtype),
            )
            pos_dec = jnp.asarray(positions, jnp.int32)
            if pos_dec.ndim == 1:
                pos_dec = jnp.broadcast_to(pos_dec[None], (b, s))
            tables = jnp.asarray(block_tables, jnp.int32)  # (b, n_pg)
            q = apply_rope(q, cos, sin, pos_dec)
            k = apply_rope(k, cos, sin, pos_dec)
            # write: logical -> physical scatter
            page_of = jnp.take_along_axis(
                tables, pos_dec // P, axis=1)              # (b, s)
            ck.value = ck.value.at[page_of, pos_dec % P].set(k)
            cv.value = cv.value.at[page_of, pos_dec % P].set(v)
            # Kernel dispatch: the injected (mesh-bound) fn wins, then
            # the single-device kernel per cfg.paged_kernel — ONE call
            # + epilogue so the contract (lens = pos+1, o_proj tail)
            # cannot drift between the two.
            kernel_fn = None
            if s == 1:
                if self.paged_attention_fn is not None:
                    kernel_fn = self.paged_attention_fn
                elif cfg.paged_kernel != "off":
                    from sparkdl_tpu.ops._dispatch import use_pallas
                    from sparkdl_tpu.ops.pallas.paged_attention import (
                        paged_attention_decode,
                    )

                    if (cfg.paged_kernel == "force_interpret"
                            or use_pallas()):
                        kernel_fn = functools.partial(
                            paged_attention_decode,
                            interpret=(cfg.paged_kernel
                                       == "force_interpret"),
                        )
            if kernel_fn is not None:
                o = kernel_fn(
                    q[:, 0], ck.value, cv.value, tables,
                    pos_dec[:, 0] + 1,
                ).reshape(b, s, cfg.n_heads * head_dim)
                return _apply_dense(cfg, cfg.d_model, "o_proj", o,
                                    adapter_ids)
            # read: gather each row's pages into its logical view
            L = tables.shape[1] * P
            k = ck.value[tables].reshape(b, L, cfg.n_kv_heads, head_dim)
            v = cv.value[tables].reshape(b, L, cfg.n_kv_heads, head_dim)
            mask = (jnp.arange(L)[None, None, :]
                    <= pos_dec[:, :, None])[:, None]       # (b,1,s,L)
            rep = cfg.n_heads // cfg.n_kv_heads
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            # input-dtype operands, fp32 accumulation (same MXU
            # discipline as attention_reference — no fp32 upcast)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k,
                preferred_element_type=jnp.float32,
            ) * (head_dim ** -0.5)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum(
                "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            ).astype(v.dtype).reshape(b, s, cfg.n_heads * head_dim)
            return _apply_dense(cfg, cfg.d_model, "o_proj", o, adapter_ids)

        if cfg.decode:
            if s > cfg.max_cache_len:
                raise ValueError(
                    f"sequence {s} exceeds max_cache_len "
                    f"{cfg.max_cache_len}"
                )
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros(
                    (b, cfg.max_cache_len, cfg.n_kv_heads, head_dim),
                    k.dtype,
                ),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(
                    (b, cfg.max_cache_len, cfg.n_kv_heads, head_dim),
                    v.dtype,
                ),
            )
            cidx = self.variable(
                "cache", "cache_index",
                lambda: jnp.zeros((), jnp.int32),
            )
            if positions is not None:
                # Slot-mapped serving (continuous batching): every
                # batch row is an independent stream at its OWN
                # position — the caller owns the per-slot position
                # vector; the shared cache index is not advanced.
                pos_dec = jnp.asarray(positions, jnp.int32)
                if pos_dec.ndim == 1:
                    pos_dec = jnp.broadcast_to(pos_dec[None], (b, s))
                q = apply_rope(q, cos, sin, pos_dec)
                k = apply_rope(k, cos, sin, pos_dec)
                bidx = jnp.arange(b)[:, None]
                ck.value = ck.value.at[bidx, pos_dec].set(k)
                cv.value = cv.value.at[bidx, pos_dec].set(v)
                mask = (jnp.arange(cfg.max_cache_len)[None, None, :]
                        <= pos_dec[:, :, None])      # (b, s, L)
                mask = mask[:, None]                 # (b, 1, s, L)
            else:
                start = cidx.value
                pos_dec = start + jnp.arange(s)
                q = apply_rope(q, cos, sin, pos_dec)
                k = apply_rope(k, cos, sin, pos_dec)
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k, (0, start, 0, 0)
                )
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v, (0, start, 0, 0)
                )
                cidx.value = start + s
                k_pos = jnp.arange(cfg.max_cache_len)
                mask = (k_pos[None, :] <= pos_dec[:, None])[None, None]
            k, v = ck.value, cv.value
            rep = cfg.n_heads // cfg.n_kv_heads
            if rep > 1:
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            # masked attention over the cache: key t visible iff
            # t <= query position; input-dtype operands with fp32
            # accumulation (no fp32 upcast of the cache read)
            scores = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k,
                preferred_element_type=jnp.float32,
            ) * (head_dim ** -0.5)
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum(
                "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            ).astype(v.dtype).reshape(b, s, cfg.n_heads * head_dim)
            return _apply_dense(cfg, cfg.d_model, "o_proj", o, adapter_ids)

        # cos=None: attention that takes no positions (a hybrid
        # decoder's, whose state-space layers carry the order)
        if cos is not None:
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
        # GQA: repeat kv heads up to n_heads
        rep = cfg.n_heads // cfg.n_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # Attention policy (cfg.attention): "reference" = XLA
        # attention with native autodiff (scores in HBM). "flash" =
        # the pallas kernels, forward and fused backward, O(S·D)
        # memory: what the on-chip benchmark's train cell runs
        # (PERF.md). Injectable attention_fn overrides both (ring
        # attention under sequence parallelism).
        if self.attention_fn is not None:
            attend = self.attention_fn
        elif cfg.attention == "flash":
            from sparkdl_tpu.ops.attention import flash_attention

            attend = lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True,
                block=cfg.flash_block or None,
            )
        else:
            from sparkdl_tpu.parallel.ring_attention import (
                attention_reference,
            )

            attend = lambda q_, k_, v_: attention_reference(
                q_, k_, v_, causal=True
            )
        o = attend(q, k, v).reshape(b, s, cfg.n_heads * head_dim)
        return _apply_dense(cfg, cfg.d_model, "o_proj", o, adapter_ids)


class MLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, cfg.d_ff, "gate_proj")(x)
        up = _dense(cfg, cfg.d_ff, "up_proj")(x)
        h = nn.silu(gate) * up
        return _dense(cfg, cfg.d_model, "down_proj")(h)


class Block(nn.Module):
    cfg: LlamaConfig
    attention_fn: Optional[Callable] = None
    use_moe: bool = False
    paged_attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, cos, sin, positions, block_tables=None,
                 adapter_ids=None):
        cfg = self.cfg
        # Fixed scope names (not flax paths, no layer index): what the
        # device trace is grouped by. Backward and remat show in JAX's
        # own name stack around them (transpose(jvp(sparkdl.attn))).
        with jax.named_scope("sparkdl.attn"):
            h = x + Attention(cfg, self.attention_fn,
                              self.paged_attention_fn, name="attn")(
                RMSNorm(cfg.rms_eps, name="attn_norm")(x), cos, sin,
                positions, block_tables=block_tables,
                adapter_ids=adapter_ids,
            )
        if self.use_moe:
            from sparkdl_tpu.models.moe import MoEConfig, MoEMLP

            mlp = MoEMLP(
                MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                          n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                          dtype=cfg.dtype),
                name="moe_mlp",
            )
        else:
            mlp = MLP(cfg, name="mlp")
        with jax.named_scope(
                "sparkdl.moe" if self.use_moe else "sparkdl.mlp"):
            return h + mlp(RMSNorm(cfg.rms_eps, name="mlp_norm")(h))


class Llama(nn.Module):
    cfg: LlamaConfig
    attention_fn: Optional[Callable] = None
    paged_attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden=False,
                 block_tables=None, adapter_ids=None):
        """``return_hidden=True`` skips the lm_head matmul and returns
        the final-norm hidden states — the input contract of
        :func:`sparkdl_tpu.parallel.train.fused_cross_entropy`, which
        fuses unembed+softmax-CE in sequence chunks. Init traces with
        the default so the param tree always contains ``lm_head``."""
        cfg = self.cfg
        b, s = tokens.shape
        if positions is None and not cfg.decode:
            positions = jnp.arange(s)
        # cfg.decode keeps a None default: the attention cache index is
        # the position source of truth there, and an EXPLICIT positions
        # array (slot-mapped continuous-batching serving) must be
        # distinguishable from the default.
        head_dim = cfg.d_model // cfg.n_heads
        # Static RoPE table covering both training (seq s) and cached
        # decoding (positions < max_cache_len).
        cos, sin = rope_freqs(
            head_dim, max(s, cfg.max_cache_len), cfg.rope_theta,
            cfg.rope_scaling,
        )
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     name="embed")(tokens)
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=())
        for i in range(cfg.n_layers):
            use_moe = (cfg.n_experts > 0
                       and i % cfg.moe_every == cfg.moe_every - 1)
            x = block(cfg, self.attention_fn, use_moe,
                      self.paged_attention_fn,
                      name=f"layer_{i}")(x, cos, sin, positions,
                                         block_tables, adapter_ids)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        if return_hidden:
            return x
        with jax.named_scope("sparkdl.lm_head_loss"):
            if cfg.quant:
                from sparkdl_tpu.models.quant import QuantDense, QuantDense4

                if cfg.quant == "int4":
                    return QuantDense4(cfg.vocab_size, dtype=jnp.float32,
                                       group=cfg.quant_group,
                                       kernel=cfg.quant_kernel,
                                       name="lm_head")(
                        x.astype(jnp.float32))
                return QuantDense(cfg.vocab_size, dtype=jnp.float32,
                                  kernel=cfg.quant_kernel,
                                  name="lm_head")(x.astype(jnp.float32))
            # fp32 head: stability for the softmax/sampling path. (A bf16
            # head was measured on v5e and did NOT beat this — XLA already
            # runs the fp32 matmul as bf16x3 passes and the extra output
            # cast costs more than the passes save at d_model 1024.)
            logits = nn.Dense(cfg.vocab_size, use_bias=False,
                              dtype=jnp.float32, name="lm_head")(
                x.astype(jnp.float32)
            )
            return logits
