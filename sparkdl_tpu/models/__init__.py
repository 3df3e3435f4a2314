"""Model zoo covering the reference's benchmark families
(BASELINE.json configs): MNIST CNN, ResNet, BERT, the Llama
decoder with LoRA, and a patterned (state-space / expert / attention)
decoder — all flax, all written for bf16 MXU math and GSPMD
sharding via :mod:`sparkdl_tpu.parallel.sharding`.

Serving-side modules (imported by path, not re-exported — they pull
decode-only machinery):

- :mod:`.generate` — cached single-stream decode (+ top-k/top-p,
  logprobs)
- :mod:`.serving` — ContinuousBatchingEngine / SpeculativeBatchingEngine
  (paged cache, prefix caching, multi-LoRA, stops, logprobs)
- :mod:`.server` — HTTP front-end over any engine
- :mod:`.fleet` — N engine replicas behind one admission-controlled
  frontend (bounded-queue 503s, least-depth routing, replica
  supervision/respawn)
- :mod:`.speculative` — single-burst speculative decode + the
  rejection-sampling core
- :mod:`.quant` — int8/int4 weight-only serving conversions
- :mod:`.convert` — HuggingFace Llama checkpoint import/export
- :mod:`.moe` — expert-parallel MoE (psum-combine and a2a dispatch),
  and one chip's share of a layer's experts by sorted dispatch
  (``relu2`` experts in a latent width, gated experts on the full one)
- :mod:`.mamba2` — the Mamba-2 state-space mixer (training)
- :mod:`.mla` — multi-head latent attention, expanded form (training)
- :mod:`.mixed_attention` — gated, QK-normed attention for decoders that
  mix window and full attention layers (training)
"""

from sparkdl_tpu.models.bert import (  # noqa: F401
    Bert,
    BertConfig,
    BertForQuestionAnswering,
    BertForSequenceClassification,
)
from sparkdl_tpu.models.hybrid import (  # noqa: F401
    HybridConfig,
    HybridDecoder,
)
from sparkdl_tpu.models.llama import Llama, LlamaConfig  # noqa: F401
from sparkdl_tpu.models.lora import lora_mask  # noqa: F401
from sparkdl_tpu.models.mnist_cnn import MnistCNN  # noqa: F401
from sparkdl_tpu.models.resnet import ResNet, ResNet50  # noqa: F401
