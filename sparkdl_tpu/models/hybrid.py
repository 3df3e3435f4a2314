"""A decoder whose layers are a PATTERN: one mixer a layer, chosen by a
letter. The ``nemotron_h`` family publishes its pattern
(``hybrid_override_pattern``: ``M``, ``E``, ``*``); a family whose
published layer is TWO residual steps, attention then feed-forward, is
two letters a published layer (``glm4_moe_lite``: ``LD`` for a leading
dense layer, ``LG`` for every other; ``afmoe``: ``S`` or ``F`` by the
layer's ``layer_types`` entry, then ``D`` or ``G``).

    M  a Mamba-2 state-space mixer       (:mod:`.mamba2`)
    E  routed experts in a latent width, and a shared expert
                                         (:class:`.moe.LatentMoE`)
    *  grouped-query causal attention    (:class:`.llama.Attention`,
       with no rotary embedding: the state-space layers carry the order)
    L  multi-head latent attention, rope on a narrow part of a head
                                         (:class:`.mla.LatentAttention`)
    S  gated grouped-query attention over a causal WINDOW
       (``sliding_window``), with rope and a norm a head on q and k
                                 (:class:`.mixed_attention.MixedAttention`)
    F  the same mixer over the whole causal prefix, without rope
    D  a dense gated MLP                 (:class:`.llama.MLP`)
    G  gated routed experts on the full width, and a shared expert
                                         (:class:`.moe.GatedMoE`)

Every layer is ``x <- x + mixer(RMSNorm(x))``, or with ``post_norm``
(``afmoe``'s sandwich) ``x <- x + RMSNorm(mixer(RMSNorm(x)))``; the
embedding is scaled by ``sqrt(d_model)`` where ``scale_embedding`` says
so; then a final norm and the head. :class:`HybridDecoder` has
:class:`.llama.Llama`'s call contract, so
:func:`sparkdl_tpu.parallel.train.make_lm_loss_fn` and
``make_train_step`` take it as they take ``Llama``.

Supported: LoRA training of ONE chip's share of a deployment that
divides each layer's routed experts and the vocabulary over chips
(``experts_held``, ``vocab_size``), or that holds them whole. Not
supported: serving (no recurrent state and no latent cache beside the
paged one; ``L`` runs its expanded form only; ``S`` has no cache that
keeps a window's pages alone), multi-token prediction, the exchange
between the chips that share a layer. docs/hybrid.rst has
the equations and the maps from published keys.
"""

import dataclasses
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from sparkdl_tpu.models.llama import MLP, Attention, LlamaConfig, RMSNorm
from sparkdl_tpu.models.mamba2 import Mamba2Mixer
from sparkdl_tpu.models.mixed_attention import MixedAttention
from sparkdl_tpu.models.mla import LatentAttention
from sparkdl_tpu.models.moe import GatedMoE, LatentMoE

# ``model_type`` -> {published key -> field}, for every key a mixer's
# shape is read from; a file without the key is ``nemotron_h``'s
PUBLISHED = {
    "nemotron_h": {
        "n_routed_experts": "n_routed_experts",
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
    },
    # the pattern and the shared expert's width are BUILT from
    # num_hidden_layers, first_k_dense_replace and n_shared_experts
    "glm4_moe_lite": {
        "n_routed_experts": "n_routed_experts",
        "vocab_size": "vocab_size", "hidden_size": "d_model",
        "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "q_lora_rank": "q_rank", "kv_lora_rank": "kv_rank",
        "qk_nope_head_dim": "qk_nope_dim", "qk_rope_head_dim": "qk_rope_dim",
        "v_head_dim": "v_dim", "rope_theta": "rope_theta",
        "intermediate_size": "dense_d_ff",
        "num_experts_per_tok": "top_k",
        "moe_intermediate_size": "expert_d_ff",
        "routed_scaling_factor": "routed_scale", "rms_norm_eps": "rms_eps",
    },
    # the pattern is BUILT from layer_types and num_dense_layers, the
    # shared expert's width from num_shared_experts; a norm follows
    # every mixer, and mup_enabled scales the embedding
    "afmoe": {
        "num_experts": "n_routed_experts",
        "vocab_size": "vocab_size", "hidden_size": "d_model",
        "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "head_dim": "head_dim", "sliding_window": "sliding_window",
        "rope_theta": "rope_theta", "intermediate_size": "dense_d_ff",
        "num_experts_per_tok": "top_k",
        "moe_intermediate_size": "expert_d_ff",
        "route_scale": "routed_scale", "rms_norm_eps": "rms_eps",
        "mup_enabled": "scale_embedding",
    },
}
LAYER_TYPES = {"sliding_attention": "S", "full_attention": "F"}
SCOPES = {"M": "sparkdl.ssm", "E": "sparkdl.moe", "*": "sparkdl.attn",
          "L": "sparkdl.mla", "D": "sparkdl.mlp", "G": "sparkdl.moe",
          "S": "sparkdl.attn", "F": "sparkdl.attn"}


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
    # latent attention: the ranks of the two latents, a head's part
    # without and with position, and a head's values
    q_rank: int = 48
    kv_rank: int = 32
    qk_nope_dim: int = 24
    qk_rope_dim: int = 8
    v_dim: int = 32
    rope_theta: float = 10000.0
    # the window of the ``S`` layers: the newest keys a query sees
    sliding_window: int = 32
    dense_d_ff: int = 128
    rms_eps: float = 1e-5
    # a second norm a layer, on the mixer's output before it is added
    post_norm: bool = False
    # the embedding times sqrt(d_model)
    scale_embedding: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = False
    attention: str = "reference"
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: Sequence[str] = ("in_proj", "out_proj", "q_proj", "v_proj")

    def __post_init__(self):
        """A mixer's shape is checked where the pattern has its
        letter."""
        has = set(self.pattern)
        if not has or has - set(SCOPES):
            raise ValueError(
                f"pattern {self.pattern!r}: one letter a layer, of "
                f"{sorted(SCOPES)}")
        if "*" in has and self.head_dim * self.n_heads != self.d_model:
            raise ValueError(
                "llama.Attention's heads are d_model / n_heads wide: "
                f"head_dim={self.head_dim} x n_heads={self.n_heads} "
                f"is not d_model={self.d_model}")
        first, count = self.experts_held
        if has & {"E", "G"} and not (
                0 <= first and count > 0
                and first + count <= self.n_routed_experts):
            raise ValueError(
                f"experts_held={self.experts_held} is no share of "
                f"n_routed_experts={self.n_routed_experts}")
        if "M" in has and self.ssm_heads % self.ssm_groups:
            raise ValueError(
                f"ssm_heads={self.ssm_heads} not divisible by "
                f"ssm_groups={self.ssm_groups}")
        if "S" in has and (self.head_dim % 2 or self.sliding_window < 1):
            raise ValueError(
                f"head_dim={self.head_dim}: rope turns pairs; "
                f"sliding_window={self.sliding_window}: a query sees itself")
        if has & {"S", "F"} and self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} not divisible by "
                f"n_kv_heads={self.n_kv_heads}")
        if "L" in has and self.qk_rope_dim % 2:
            raise ValueError(
                f"qk_rope_dim={self.qk_rope_dim}: rope turns pairs")
        if ("L" in has and self.attention == "flash"
                and self.v_dim != self.qk_nope_dim + self.qk_rope_dim):
            raise ValueError(
                "the flash kernels take one head size: "
                f"v_dim={self.v_dim} is not qk_nope_dim + qk_rope_dim="
                f"{self.qk_nope_dim + self.qk_rope_dim}")

    @classmethod
    def from_published(cls, config, **kw):
        """From a ``config.json``'s keys (a dict), by the map of its
        ``model_type``. Where the file holds a chip's share,
        ``n_routed_experts`` there is the experts held: pass the
        router's width and ``experts_held``."""
        kind = config.get("model_type", "nemotron_h")
        fields = {field: config[key] for key, field in PUBLISHED[kind].items()}
        if kind == "glm4_moe_lite":
            dense = config["first_k_dense_replace"]
            fields["pattern"] = "LD" * dense + "LG" * (
                config["num_hidden_layers"] - dense)
            fields["shared_d_ff"] = (config["n_shared_experts"]
                                     * config["moe_intermediate_size"])
        if kind == "afmoe":
            types = config["layer_types"]
            if len(types) != config["num_hidden_layers"]:
                raise ValueError(
                    f"layer_types has {len(types)} entries for "
                    f"num_hidden_layers={config['num_hidden_layers']}")
            fields["pattern"] = "".join(
                LAYER_TYPES[t] + ("D" if i < config["num_dense_layers"]
                                  else "G") for i, t in enumerate(types))
            fields["shared_d_ff"] = (config["num_shared_experts"]
                                     * config["moe_intermediate_size"])
            fields["post_norm"] = True
        return cls(**{"experts_held": (0, fields["n_routed_experts"]),
                      **fields, **kw})

    @property
    def attn(self):
        """What ``llama.Attention``, ``llama.MLP`` and ``llama._dense``
        read."""
        return LlamaConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=len(self.pattern), n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_ff=self.dense_d_ff,
            rms_eps=self.rms_eps,
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
                h = Mamba2Mixer(cfg, name="mamba")(h)
            elif self.kind == "E":
                h = LatentMoE(cfg, name="moe")(h)
            elif self.kind == "L":
                h = LatentAttention(cfg, name="mla")(h)
            elif self.kind == "D":
                h = MLP(cfg.attn, name="mlp")(h)
            elif self.kind == "G":
                h = GatedMoE(cfg, name="moe")(h)
            elif self.kind in "SF":
                h = MixedAttention(
                    cfg, cfg.sliding_window if self.kind == "S" else None,
                    name="attn")(h)
            else:
                h = Attention(cfg.attn, name="attn")(h, None, None, None)
            if cfg.post_norm:
                h = RMSNorm(cfg.rms_eps, name="post_norm")(h)
            return x + h


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
        if cfg.scale_embedding:
            x = x * cfg.d_model ** 0.5
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
