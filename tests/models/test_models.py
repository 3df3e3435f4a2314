"""Model-zoo correctness: shapes, finite losses, and one training step
for each family in BASELINE.json."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from sparkdl_tpu.parallel.train import cross_entropy_loss, make_train_step


def _train_a_bit(model, params, batch_fn, loss_fn, steps=3):
    opt = optax.adam(1e-2)
    step = jax.jit(make_train_step(loss_fn, opt))
    state = opt.init(params)
    losses = []
    for i in range(steps):
        params, state, m = step(params, state, batch_fn(i))
        losses.append(float(m["loss"]))
    return losses


def test_mnist_cnn_trains():
    from sparkdl_tpu.models import MnistCNN

    model = MnistCNN()
    rng = np.random.default_rng(0)
    x0 = jnp.zeros((8, 28, 28, 1), jnp.float32)
    params = model.init(jax.random.PRNGKey(0), x0)["params"]

    def batch_fn(i):
        x = jnp.asarray(rng.normal(size=(8, 28, 28, 1)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 10, (8,)), jnp.int32)
        return {"x": x, "y": y}

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["x"])
        return cross_entropy_loss(logits, b["y"])

    losses = _train_a_bit(model, params, batch_fn, loss_fn)
    assert all(np.isfinite(losses))


def test_resnet_forward_and_bn_state():
    from sparkdl_tpu.models.resnet import ResNet18Thin

    model = ResNet18Thin(num_classes=10)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "batch_stats" in variables
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    # train mode mutates batch stats
    logits, mutated = model.apply(
        variables, jnp.ones_like(x), train=True, mutable=["batch_stats"]
    )
    assert np.isfinite(np.asarray(logits)).all()
    before = jax.tree.leaves(variables["batch_stats"])
    after = jax.tree.leaves(mutated["batch_stats"])
    assert any(
        not np.allclose(b, a) for b, a in zip(before, after)
    )


def test_resnet50_param_count():
    """ResNet-50 must be the real thing: ~25.5M params."""
    from sparkdl_tpu.models import ResNet50

    model = ResNet50(num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 224, 224, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False)
    )
    n = sum(int(np.prod(p.shape))
            for p in jax.tree.leaves(variables["params"]))
    assert 25_000_000 < n < 26_000_000, n


def test_bert_qa_heads_and_mask():
    from sparkdl_tpu.models import BertConfig, BertForQuestionAnswering

    cfg = BertConfig.tiny(dtype=jnp.float32)
    model = BertForQuestionAnswering(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    mask = jnp.concatenate(
        [jnp.ones((2, 12), bool), jnp.zeros((2, 4), bool)], axis=1
    )
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    start, end = model.apply({"params": params}, ids, attention_mask=mask)
    assert start.shape == (2, 16) and end.shape == (2, 16)
    assert np.isfinite(np.asarray(start)).all()


def test_bert_trains_on_classification():
    from sparkdl_tpu.models import BertConfig, BertForSequenceClassification

    cfg = BertConfig.tiny(dtype=jnp.float32)
    model = BertForSequenceClassification(cfg, num_classes=2)
    rng = np.random.default_rng(0)
    ids0 = jnp.zeros((4, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids0)["params"]

    # fixed batch: training must be able to memorize it
    ids_fixed = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)),
                            jnp.int32)
    fixed = {"ids": ids_fixed, "y": (ids_fixed[:, 0] % 2).astype(jnp.int32)}

    def batch_fn(i):
        return fixed

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["ids"])
        return cross_entropy_loss(logits, b["y"])

    losses = _train_a_bit(model, params, batch_fn, loss_fn, steps=10)
    assert losses[-1] < losses[0]


def test_llama_causality():
    """Changing a future token must not affect earlier logits."""
    from sparkdl_tpu.models import Llama, LlamaConfig

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = Llama(cfg)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 12)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    out1 = model.apply({"params": params}, ids)
    ids2 = ids.at[0, -1].set((ids[0, -1] + 1) % cfg.vocab_size)
    out2 = model.apply({"params": params}, ids2)
    np.testing.assert_allclose(
        np.asarray(out1[0, :-1]), np.asarray(out2[0, :-1]), atol=1e-5
    )
    assert not np.allclose(np.asarray(out1[0, -1]), np.asarray(out2[0, -1]))


@pytest.mark.parametrize("preset,rope", [
    ("llama3_8b", None), ("llama31_8b", ("llama3", 8.0, 1.0, 4.0, 8192))])
def test_llama_8b_presets_take_overrides(preset, rope):
    """The published-shape presets accept overrides of the fields they
    set themselves: a depth cut is ``n_layers=``, widths untouched."""
    from sparkdl_tpu.models import LlamaConfig

    cfg = getattr(LlamaConfig, preset)(n_layers=4, lora_rank=8)
    assert (cfg.n_layers, cfg.lora_rank, cfg.rope_scaling) == (4, 8, rope)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size) == (4096, 32, 8, 14336, 128256)
    assert getattr(LlamaConfig, preset)().n_layers == 32


def test_lora_merge_equivalence():
    """merge_lora_with folds adapters: merged plain forward == LoRA
    forward."""
    from sparkdl_tpu.models import Llama, LlamaConfig
    from sparkdl_tpu.models.lora import merge_lora_with

    cfg = LlamaConfig.tiny(lora_rank=4, lora_alpha=8.0, dtype=jnp.float32)
    model = Llama(cfg)
    ids = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    # make adapters nonzero
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 0.01
        if any("lora_b" == str(getattr(p, "key", "")) for p in path) else x,
        params,
    )
    out_lora = model.apply({"params": params}, ids)
    merged = merge_lora_with(params, alpha=cfg.lora_alpha, rank=cfg.lora_rank)
    out_merged = model.apply({"params": merged}, ids)
    np.testing.assert_allclose(
        np.asarray(out_lora), np.asarray(out_merged), atol=1e-5
    )


def test_bert_params_shard_with_transformer_rules():
    """BERT module names align with the tensor-parallel sharding rules
    (q_proj/fc1 column-parallel, o_proj/fc2 row-parallel)."""
    from sparkdl_tpu.models import Bert, BertConfig
    from sparkdl_tpu.parallel.mesh import MeshSpec, make_mesh
    from sparkdl_tpu.parallel.sharding import (
        TRANSFORMER_RULES,
        param_sharding,
    )

    cfg = BertConfig.tiny(dtype=jnp.float32)
    model = Bert(cfg)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    mesh = make_mesh(MeshSpec(data=4, model=2))
    shardings = param_sharding(params, TRANSFORMER_RULES, mesh)
    flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    by_name = {
        "/".join(str(getattr(p, "key", p)) for p in path): s
        for path, s in flat
    }
    fc1 = next(v for k, v in by_name.items() if "fc1/kernel" in k)
    assert "model" in str(fc1.spec)
    ln = next(v for k, v in by_name.items() if "attn_norm/scale" in k)
    assert ln.spec == jax.sharding.PartitionSpec()
