"""A decoder whose layers are a PATTERN: one mixer a layer, chosen by a
letter (the ``nemotron_h`` family's ``hybrid_override_pattern``).

    M  a Mamba-2 state-space mixer       (:mod:`.mamba2`)
    E  routed experts in a latent width, and a shared expert
                                         (:class:`.moe.LatentMoE`)
    *  grouped-query causal attention    (:class:`.llama.Attention`,
       with no rotary embedding: the state-space layers carry the order)

Every layer is ``x <- x + mixer(RMSNorm(x))``; then a final norm and the
head. :class:`HybridDecoder` has :class:`.llama.Llama`'s call contract,
so :func:`sparkdl_tpu.parallel.train.make_lm_loss_fn` and
``make_train_step`` take it as they take ``Llama``.

Supported: LoRA training of ONE chip's share of a deployment that
divides each layer's routed experts and the vocabulary over chips
(``experts_held``, ``vocab_size``). Not supported: serving (no recurrent
state beside the cache), multi-token prediction, the exchange between
the chips that share a layer. docs/hybrid.rst has the equations and the
map from published keys.
"""

import dataclasses
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.llama import Attention, LlamaConfig, RMSNorm
from sparkdl_tpu.models.mamba2 import Mamba2Mixer
from sparkdl_tpu.models.moe import LatentMoE

# published key -> field, for every key a mixer's shape is read from
PUBLISHED = {
    "vocab_size": "vocab_size", "hidden_size": "d_model",
    "hybrid_override_pattern": "pattern",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "mamba_num_heads": "ssm_heads", "mamba_head_dim": "ssm_head_dim",
    "n_groups": "ssm_groups", "ssm_state_size": "ssm_state",
    "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
    "time_step_min": "time_step_min", "time_step_max": "time_step_max",
    "time_step_floor": "time_step_floor",
    "num_experts_per_tok": "top_k", "moe_latent_size": "latent",
    "moe_intermediate_size": "expert_d_ff",
    "moe_shared_expert_intermediate_size": "shared_d_ff",
    "routed_scaling_factor": "routed_scale", "norm_eps": "rms_eps",
}
SCOPES = {"M": "sparkdl.ssm", "E": "sparkdl.moe", "*": "sparkdl.attn"}


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    pattern: str = "ME*"
    vocab_size: int = 256
    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    ssm_heads: int = 8
    ssm_head_dim: int = 16
    ssm_groups: int = 2
    ssm_state: int = 16
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # the router's width: every routed expert of the deployment
    n_routed_experts: int = 8
    # (first, count): the routed experts THIS chip holds of them
    experts_held: tuple = (0, 8)
    top_k: int = 2
    latent: int = 32
    expert_d_ff: int = 48
    shared_d_ff: int = 96
    routed_scale: float = 1.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "reference"
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Sequence[str] = ("in_proj", "out_proj", "q_proj", "v_proj")

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(SCOPES):
            raise ValueError(
                f"pattern {self.pattern!r}: one letter a layer, of "
                f"{sorted(SCOPES)}")
        if self.head_dim * self.n_heads != self.d_model:
            raise ValueError(
                "llama.Attention's heads are d_model / n_heads wide: "
                f"head_dim={self.head_dim} x n_heads={self.n_heads} "
                f"is not d_model={self.d_model}")
        first, count = self.experts_held
        if not (0 <= first and count > 0
                and first + count <= self.n_routed_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is no share of "
                f"n_routed_experts={self.n_routed_experts}")
        if self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"ssm_heads={self.ssm_heads} not divisible by "
                f"ssm_groups={self.ssm_groups}")

    @classmethod
    def from_published(cls, config, **kw):
        """From a ``nemotron_h`` ``config.json``'s keys (a dict). Where
        the file holds a chip's share, ``n_routed_experts`` there is the
        experts held: pass the router's width and ``experts_held``."""
        fields = {field: config[key] for key, field in PUBLISHED.items()}
        held = config["n_routed_experts"]
        return cls(**{"n_routed_experts": held, "experts_held": (0, held),
                      **fields, **kw})

    @property
    def attn(self):
        """What ``llama.Attention`` and ``llama._dense`` read."""
        return LlamaConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=len(self.pattern), n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, rms_eps=self.rms_eps,
            dtype=self.dtype, attention=self.attention,
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            lora_targets=tuple(self.lora_targets))


class HybridLayer(nn.Module):
    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with jax.named_scope(SCOPES[self.kind]):
            h = RMSNorm(cfg.rms_eps, name="norm")(x)
            if self.kind == "M":
                return x + Mamba2Mixer(cfg, name="mamba")(h)
            if self.kind == "E":
                return x + LatentMoE(cfg, name="moe")(h)
            return x + Attention(cfg.attn, name="attn")(
                h, None, None, None)


class HybridDecoder(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        """Logits (batch, seq, vocab) in float32, or with
        ``return_hidden`` the final-norm hidden states, as
        :class:`.llama.Llama` returns them."""
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     name="embed")(tokens)
        layer = nn.remat(HybridLayer) if cfg.remat else HybridLayer
        for i, kind in enumerate(cfg.pattern):
            x = layer(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(cfg.rms_eps, name="final_norm")(x)
        if return_hidden:
            return x
        with jax.named_scope("sparkdl.lm_head_loss"):
            return nn.Dense(cfg.vocab_size, use_bias=False,
                            dtype=jnp.float32, name="lm_head")(
                x.astype(jnp.float32))
