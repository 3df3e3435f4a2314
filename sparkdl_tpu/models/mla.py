"""Multi-head latent attention (MLA), the DeepSeek-V2 family's, as
GLM-4.7-Flash (``glm4_moe_lite``) has it: queries and keys/values go
through low-rank latents with an RMSNorm on each, and rope turns a
narrow part of each head only, whose key is ONE vector a token shared
by every head.

    c_q = RMSNorm(W_qa x);   [q_nope | q_rope] = W_qb c_q     a head
    [c_kv | k_rope] = W_kva x;   c_kv = RMSNorm(c_kv)
    [k_nope | v] = W_kvb c_kv                                  a head
    q = [q_nope | rope(q_rope)];   k = [k_nope | rope(k_rope)]
    out = W_o softmax(q k^T / sqrt(nope + rope), causal) v

This is the EXPANDED form, training's and prefill's: keys and values a
head are materialised and go to the attention kernels as plain
multi-head attention at head size ``nope + rope``. The absorbed form
and the latent cache are decoding's and are not built (PERF.md,
section 7). The five projections are ``llama._dense``'s under their
published names, so LoRA reaches each.
"""

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu import observe
from sparkdl_tpu.models.llama import RMSNorm, _dense, apply_rope, rope_freqs


class LatentAttention(nn.Module):
    """``cfg`` is a :class:`~sparkdl_tpu.models.hybrid.HybridConfig`."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = functools.partial(_dense, cfg.attn)
        b, s, _ = x.shape
        heads, nope, rope, v_dim = (
            cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim)
        # once a traced mixer: the shapes it was built with
        observe.inc("mla.attention", heads=heads, qk_nope=nope, qk_rope=rope,
                    v=v_dim, q_rank=cfg.q_rank, kv_rank=cfg.kv_rank,
                    form="expanded")
        with jax.named_scope("sparkdl.mla.latent"):
            c_q = RMSNorm(cfg.rms_eps, name="q_a_layernorm")(
                dense(cfg.q_rank, "q_a_proj")(x))
            q_nope, q_rope = jnp.split(
                dense(heads * (nope + rope), "q_b_proj")(c_q).reshape(
                    b, s, heads, nope + rope), [nope], axis=-1)
            c_kv, k_rope = jnp.split(
                dense(cfg.kv_rank + rope, "kv_a_proj_with_mqa")(x),
                [cfg.kv_rank], axis=-1)
            c_kv = RMSNorm(cfg.rms_eps, name="kv_a_layernorm")(c_kv)
            k_nope, v = jnp.split(
                dense(heads * (nope + v_dim), "kv_b_proj")(c_kv).reshape(
                    b, s, heads, nope + v_dim), [nope], axis=-1)
            cos, sin = rope_freqs(rope, s, cfg.rope_theta)
            positions = jnp.arange(s)
            q_rope = apply_rope(q_rope, cos, sin, positions)
            # the shared key is turned ONCE, then handed to every head
            k_rope = apply_rope(k_rope[:, :, None, :], cos, sin, positions)
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (b, s, heads, rope))],
                axis=-1)
        with jax.named_scope("sparkdl.mla.core"):
            if cfg.attention == "flash":
                from sparkdl_tpu.ops.attention import flash_attention as attend
            else:
                from sparkdl_tpu.parallel.ring_attention import (
                    attention_reference as attend,
                )
            o = attend(q, k, v, causal=True, scale=(nope + rope) ** -0.5)
        return dense(x.shape[-1], "o_proj")(o.reshape(b, s, heads * v_dim))
