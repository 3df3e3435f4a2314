"""LoRA: low-rank adapters for parameter-efficient fine-tuning (the
Llama-3-8B LoRA north-star config, BASELINE.json).

TPU framing: the frozen base matmul stays a full-width bf16 MXU op; the
adapter path is two skinny matmuls XLA fuses into the same HBM pass.
Only ``lora_a``/``lora_b`` receive gradients — enforce with
:func:`lora_mask` + the ``param_mask`` option of
:func:`sparkdl_tpu.parallel.train.make_train_step` (or optax.masked).
"""

import flax.linen as nn
import jax
import jax.numpy as jnp


class LoRADense(nn.Module):
    """Dense layer with a low-rank residual adapter:
    ``y = x @ W + (alpha/rank) * (x @ A) @ B``."""

    features: int
    rank: int = 8
    alpha: float = 16.0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d_in = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (d_in, self.features)
        ).astype(self.dtype)
        lora_a = self.param(
            "lora_a", nn.initializers.normal(stddev=0.02),
            (d_in, self.rank),
        ).astype(self.dtype)
        lora_b = self.param(
            "lora_b", nn.initializers.zeros, (self.rank, self.features)
        ).astype(self.dtype)
        base = x @ kernel
        with jax.named_scope("sparkdl.lora"):
            delta = (self.alpha / self.rank) * ((x @ lora_a) @ lora_b)
        return base + delta


class MultiLoRADense(nn.Module):
    """Serving-side multi-adapter dense: ``n_adapters`` independent
    low-rank adapters stacked on one frozen base kernel, selected
    PER BATCH ROW (S-LoRA-style multi-tenant serving — one engine, one
    base model, many fine-tunes). ``ids``: (batch,) int32 adapter
    index per row."""

    features: int
    rank: int = 8
    alpha: float = 16.0
    n_adapters: int = 1
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, ids):
        d_in = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (d_in, self.features)
        ).astype(self.dtype)
        lora_a = self.param(
            "lora_a", nn.initializers.normal(stddev=0.02),
            (self.n_adapters, d_in, self.rank),
        ).astype(self.dtype)
        lora_b = self.param(
            "lora_b", nn.initializers.zeros,
            (self.n_adapters, self.rank, self.features),
        ).astype(self.dtype)
        base = x @ kernel
        # gather each row's adapter, then two skinny batched matmuls
        with jax.named_scope("sparkdl.lora"):
            a_sel = lora_a[ids]                       # (b, d_in, r)
            b_sel = lora_b[ids]                       # (b, r, f)
            delta = jnp.einsum("bsd,bdr->bsr", x, a_sel)
            delta = jnp.einsum("bsr,brf->bsf", delta, b_sel)
            delta = (self.alpha / self.rank) * delta
        return base + delta


def stack_lora_adapters(param_trees):
    """Build ONE multi-adapter tree from N single-adapter trees that
    share a base: every ``lora_a``/``lora_b`` leaf becomes a stacked
    (N, ...) leaf; base leaves must be IDENTICAL across trees (same
    frozen model) and are taken from the first."""
    import numpy as np

    first = param_trees[0]

    def build(path, leaf, *rest):
        keys = [str(getattr(p, "key", "")) for p in path]
        if any(k in ("lora_a", "lora_b") for k in keys):
            return jnp.stack([leaf, *rest])
        for other in rest:
            if not np.array_equal(np.asarray(leaf), np.asarray(other)):
                raise ValueError(
                    f"base param {'/'.join(keys)} differs across "
                    "adapter trees — multi-LoRA serves ONE frozen base"
                )
        return leaf

    return jax.tree_util.tree_map_with_path(build, first, *param_trees[1:])


def lora_mask(params, extra_trainable=()):
    """Bool pytree: True only for lora_a/lora_b leaves (plus any param
    whose path contains one of ``extra_trainable``)."""

    def mask_leaf(path, _):
        keys = [str(getattr(p, "key", "")) for p in path]
        if any(k in ("lora_a", "lora_b") for k in keys):
            return True
        return any(any(t in k for k in keys) for t in extra_trainable)

    return jax.tree_util.tree_map_with_path(mask_leaf, params)


def merge_lora_with(params, alpha, rank):
    """Fold adapters into base kernels for deployment:
    ``kernel += (alpha/rank)·A@B``, adapters zeroed. The (alpha, rank)
    used in training must be passed explicitly."""
    def merge(node):
        if isinstance(node, dict) and "lora_a" in node and "kernel" in node:
            node = dict(node)
            node["kernel"] = node["kernel"] + (alpha / rank) * (
                node["lora_a"] @ node["lora_b"]
            )
            node["lora_a"] = jnp.zeros_like(node["lora_a"])
            node["lora_b"] = jnp.zeros_like(node["lora_b"])
            return node
        if isinstance(node, dict):
            return {k: merge(v) for k, v in node.items()}
        return node

    return merge(jax.tree.map(lambda x: x, params))
