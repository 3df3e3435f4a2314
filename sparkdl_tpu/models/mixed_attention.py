"""Gated grouped-query attention for decoders that MIX window and full
attention layers, as Trinity-Mini (``afmoe``) has it: a head size given
outright (heads x head size need not be the hidden size), an RMSNorm a
head on queries and keys, rope on the window layers and none on the
full ones, and a sigmoid gate on the heads' output before ``o_proj``.

    q = W_q x   k = W_k x   v = W_v x   g = W_g x
    q = RMSNorm_q(q), k = RMSNorm_k(k)        a head; one weight vector
    window layers: q, k = rope(q), rope(k)
    o = softmax(q k^T / sqrt(head), j <= i and, in a window layer,
                i - j < window) v
    out = W_o (o * sigmoid(g))

Training's and prefill's form only: no cache. The five projections are
``llama._dense``'s under their published names (``q_proj``, ``k_proj``,
``v_proj``, ``o_proj``, ``gate_proj``), so LoRA reaches each. The
window goes to the attention kernels, which walk only the tiles it
leaves visible (:mod:`sparkdl_tpu.ops.pallas.flash_attention`).
"""

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu import observe
from sparkdl_tpu.models.llama import RMSNorm, _dense, apply_rope, rope_freqs


class MixedAttention(nn.Module):
    """``cfg`` is a :class:`~sparkdl_tpu.models.hybrid.HybridConfig`;
    ``window`` None is a full layer, which takes no positions."""

    cfg: Any
    window: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        cfg, window = self.cfg, self.window
        dense = functools.partial(_dense, cfg.attn)
        b, s, _ = x.shape
        heads, kv_heads, head = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        # once a traced mixer: the shapes it was built with
        observe.inc("attn.mixed", heads=heads, kv_heads=kv_heads,
                    head_dim=head, window=window or 0,
                    rope=window is not None, gate=True, qk_norm=True)
        q = dense(heads * head, "q_proj")(x).reshape(b, s, heads, head)
        k = dense(kv_heads * head, "k_proj")(x).reshape(b, s, kv_heads, head)
        v = dense(kv_heads * head, "v_proj")(x).reshape(b, s, kv_heads, head)
        gate = dense(heads * head, "gate_proj")(x)
        with jax.named_scope("sparkdl.attn.qknorm"):
            q = RMSNorm(cfg.rms_eps, name="q_norm")(q)
            k = RMSNorm(cfg.rms_eps, name="k_norm")(k)
        with jax.named_scope(
                "sparkdl.attn.full" if window is None
                else "sparkdl.attn.window"):
            if window is not None:
                cos, sin = rope_freqs(head, s, cfg.rope_theta)
                positions = jnp.arange(s)
                q = apply_rope(q, cos, sin, positions)
                k = apply_rope(k, cos, sin, positions)
            if heads != kv_heads:
                k = jnp.repeat(k, heads // kv_heads, axis=2)
                v = jnp.repeat(v, heads // kv_heads, axis=2)
            if cfg.attention == "flash":
                from sparkdl_tpu.ops.attention import flash_attention as attend
            else:
                from sparkdl_tpu.parallel.ring_attention import (
                    attention_reference as attend,
                )
            o = attend(q, k, v, causal=True, window=window)
        with jax.named_scope("sparkdl.attn.gate"):
            o = o.reshape(b, s, heads * head) * nn.sigmoid(gate)
        return dense(x.shape[-1], "o_proj")(o)
