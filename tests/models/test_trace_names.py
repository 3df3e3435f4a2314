"""The names the program gives its own work, where a trace reads them:
fixed ``jax.named_scope`` names on the model's parts (in the lowered
train step's debug locations) and a fixed ``name=`` on every
``pallas_call`` of the main path (in the jaxpr). Metadata only."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.models import Llama, LlamaConfig, lora_mask
from sparkdl_tpu.parallel.train import (
    global_batch,
    make_lm_loss_fn,
    make_train_step,
)

SCOPES = ("sparkdl.attn", "sparkdl.mlp", "sparkdl.lora",
          "sparkdl.lm_head_loss", "sparkdl.optimizer")


def _lowered_step(loss, **cfg_kw):
    cfg = LlamaConfig.tiny(lora_rank=4, lora_targets=("q_proj", "v_proj"),
                           remat=True, **cfg_kw)
    model = Llama(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    mask = lora_mask(params)
    opt = optax.masked(optax.adamw(1e-3), mask)
    step = make_train_step(make_lm_loss_fn(model, loss=loss, chunk=8),
                           opt, param_mask=mask)
    batch = jax.tree.map(jnp.asarray, global_batch(
        np.random.default_rng(0), cfg.vocab_size, 2, 16))
    return jax.jit(step).lower(params, opt.init(params), batch)


@pytest.mark.parametrize("loss", ["fused", "logits"])
def test_the_lowered_train_step_holds_each_scope(loss):
    text = _lowered_step(loss).as_text(debug_info=True)
    for scope in SCOPES:
        assert f"{scope}/" in text or f"{scope})" in text, scope
    # one scope gives forward, backward and recompute: JAX's own name
    # stack says which around it
    assert "jvp(Llama)/layer_0/sparkdl.attn/" in text
    assert "transpose(jvp(Llama))" in text
    assert "rematted_computation/layer_1/sparkdl.mlp/" in text
    # the adapter's scope sits inside the attention's
    assert "sparkdl.attn/attn/q_proj/sparkdl.lora" in text
    # no layer index and no flax path in a scope's own name
    assert "sparkdl.layer" not in text


def test_the_experts_scope():
    text = _lowered_step(
        "fused", n_experts=4, moe_top_k=2, moe_every=1).as_text(
            debug_info=True)
    assert "layer_0/sparkdl.moe/" in text and "sparkdl.mlp" not in text


def _kernel_names(jaxpr):
    """``name`` of every pallas_call in `jaxpr`, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_names(sub)
    return found


def test_flash_forward_and_backward_carry_their_names():
    from sparkdl_tpu.ops.attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).sum(), argnums=(0, 1, 2))(q, k, v)

    assert _kernel_names(jax.make_jaxpr(fwd)(q, q, q).jaxpr) == [
        "sparkdl_flash_fwd"]
    assert sorted(_kernel_names(jax.make_jaxpr(bwd)(q, q, q).jaxpr)) == [
        "sparkdl_flash_dkv", "sparkdl_flash_dq", "sparkdl_flash_fwd"]


def test_paged_and_quantised_kernels_carry_their_names():
    from sparkdl_tpu.ops.pallas.paged_attention import paged_attention_decode
    from sparkdl_tpu.ops.pallas.quantized_matmul import (
        INT4_GROUP,
        quantized_matmul_int4_pallas,
        quantized_matmul_pallas,
    )

    pool = jnp.ones((8, 16, 2, 128), jnp.float32)
    paged = jax.make_jaxpr(
        lambda *a: paged_attention_decode(*a, interpret=True))(
        jnp.ones((2, 4, 128)), pool, pool,
        jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32))
    assert _kernel_names(paged.jaxpr) == ["sparkdl_paged_decode"]
    x = jnp.ones((8, 256), jnp.bfloat16)
    int8 = jax.make_jaxpr(
        lambda *a: quantized_matmul_pallas(*a, interpret=True))(
        x, jnp.ones((256, 128), jnp.int8), jnp.ones((128,), jnp.float32))
    assert _kernel_names(int8.jaxpr) == ["sparkdl_qmm_int8"]
    int4 = jax.make_jaxpr(
        lambda *a: quantized_matmul_int4_pallas(*a, interpret=True))(
        x, jnp.ones((128, 128), jnp.int8),
        jnp.ones((256 // INT4_GROUP, 128), jnp.float32))
    assert _kernel_names(int4.jaxpr) == ["sparkdl_qmm_int4"]
