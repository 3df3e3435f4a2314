"""fused_cross_entropy: the chunked unembed+softmax-CE used by the
flagship bench must match the materialize-the-logits reference path
(value AND gradients) — it is a pure memory-layout optimization.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import Llama, LlamaConfig
from sparkdl_tpu.parallel.mesh import MeshSpec, make_mesh
from sparkdl_tpu.parallel.train import (
    cross_entropy_loss,
    fused_cross_entropy,
    shard_batch,
)

B, S, D, V = 2, 12, 16, 37  # S deliberately not divisible by chunk


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.normal(size=(B, S, D)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(D, V)) * 0.1, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    return hidden, w, labels


def _reference(hidden, w, labels, **kw):
    return cross_entropy_loss(hidden @ w, labels, **kw)


@pytest.mark.parametrize("chunk", [5, 8, 64])
def test_value_matches_reference(data, chunk):
    hidden, w, labels = data
    ref = _reference(hidden, w, labels)
    got = fused_cross_entropy(hidden, w, labels, chunk_size=chunk)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)


def test_grads_match_reference(data):
    hidden, w, labels = data
    g_ref = jax.grad(_reference, argnums=(0, 1))(hidden, w, labels)
    g_fused = jax.grad(
        lambda h, w_: fused_cross_entropy(h, w_, labels, chunk_size=5),
        argnums=(0, 1),
    )(hidden, w)
    for a, b in zip(g_fused, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6)


def test_matmul_dtype_bf16_close_to_reference(data):
    """The ce_bf16 bench variant: bf16 operands, fp32 accumulation."""
    hidden, w, labels = data
    ref = _reference(hidden, w, labels)
    got = fused_cross_entropy(hidden, w, labels, chunk_size=8,
                              matmul_dtype=jnp.bfloat16)
    np.testing.assert_allclose(float(got), float(ref), rtol=2e-2)
    # gradients flow to both operands through the cast
    gh, gw = jax.grad(
        lambda h, w_: fused_cross_entropy(
            h, w_, labels, chunk_size=8, matmul_dtype=jnp.bfloat16
        ),
        argnums=(0, 1),
    )(hidden, w)
    g_ref = jax.grad(_reference, argnums=(0, 1))(hidden, w, labels)
    for a, b in zip((gh, gw), g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-2)


def test_ignore_index(data):
    hidden, w, labels = data
    labels = labels.at[:, ::3].set(-1)
    ref = _reference(hidden, w, labels, ignore_index=-1)
    got = fused_cross_entropy(hidden, w, labels, chunk_size=4,
                              ignore_index=-1)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)


def test_freeze_head_zeroes_w_grad(data):
    hidden, w, labels = data
    gh, gw = jax.grad(
        lambda h, w_: fused_cross_entropy(
            h, w_, labels, chunk_size=8, freeze_head=True
        ),
        argnums=(0, 1),
    )(hidden, w)
    assert np.any(np.asarray(gh))        # activations still flow
    assert not np.any(np.asarray(gw))    # head frozen


def _frozen(hidden, w, labels, **kw):
    return fused_cross_entropy(hidden, w, labels, freeze_head=True, **kw)


@pytest.mark.parametrize("chunk", [5, 8, 64])
def test_frozen_head_matches_reference(data, chunk):
    """The one-pass form: the hidden states' gradient is made in the
    forward scan, beside each chunk's logits."""
    hidden, w, labels = data
    ref, g_ref = jax.value_and_grad(_reference)(hidden, w, labels)
    got, g = jax.value_and_grad(_frozen)(hidden, w, labels,
                                         chunk_size=chunk)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    assert g.shape == hidden.shape and g.dtype == hidden.dtype
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)


def test_frozen_head_ignore_index(data):
    hidden, w, labels = data
    labels = labels.at[:, ::3].set(-1)
    ref, g_ref = jax.value_and_grad(_reference)(
        hidden, w, labels, ignore_index=-1)
    got, g = jax.value_and_grad(_frozen)(
        hidden, w, labels, chunk_size=5, ignore_index=-1)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-6)
    assert not np.any(np.asarray(g)[:, ::3])     # an ignored token's row


def test_frozen_head_matmul_dtype_bf16_close_to_reference(data):
    hidden, w, labels = data
    ref, g_ref = jax.value_and_grad(_reference)(hidden, w, labels)
    got, g = jax.value_and_grad(_frozen)(
        hidden, w, labels, chunk_size=8, matmul_dtype=jnp.bfloat16)
    np.testing.assert_allclose(float(got), float(ref), rtol=2e-2)
    assert g.dtype == hidden.dtype
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=3e-2)
    # bf16 hidden states, as the cells have them: a bf16 gradient
    g16 = jax.grad(_frozen)(hidden.astype(jnp.bfloat16), w, labels,
                            chunk_size=8, matmul_dtype=jnp.bfloat16)
    assert g16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g16, np.float32),
                               np.asarray(g_ref), atol=3e-2)


def test_frozen_head_under_an_upstream_scale(data):
    """The backward rule is the saved gradient times what arrives from
    upstream: a scale other than 1 shows that it is applied."""
    hidden, w, labels = data
    g_ref = jax.grad(lambda h: 3.0 * _reference(h, w, labels))(hidden)
    g = jax.grad(lambda h: 3.0 * _frozen(h, w, labels, chunk_size=5))(hidden)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=3e-6)


@pytest.mark.parametrize("matmul_dtype", [None, jnp.bfloat16])
def test_one_pass_and_recompute_agree(data, matmul_dtype):
    """The two forms are one expression: the frozen head's makes the
    gradient once, the trainable head's recomputes the logits for it."""
    hidden, w, labels = data
    labels = labels.at[0, 1].set(-1)
    kw = dict(chunk_size=5, ignore_index=-1, matmul_dtype=matmul_dtype)
    one, g_one = jax.value_and_grad(_frozen)(hidden, w, labels, **kw)
    two, g_two = jax.value_and_grad(fused_cross_entropy)(
        hidden, w, labels, **kw)
    np.testing.assert_allclose(float(one), float(two), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g_one), np.asarray(g_two),
                               atol=1e-6 if matmul_dtype is None else 2e-3)


def _products_over_the_head(fn, *args):
    """``dot_general`` equations of `fn`'s jaxpr, inner jaxprs too, that
    take the ``(D, V)`` head: each is one pass over it."""
    def walk(jaxpr):
        found = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found += any(v.aval.shape == (D, V) for v in eqn.invars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += walk(sub)
        return found
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def test_a_frozen_head_is_passed_over_twice_a_step(data):
    """Logits and the hidden states' gradient: two products over the
    head under ``value_and_grad``, where the recompute form has three;
    the loss alone, with no gradient asked for, has one."""
    hidden, w, labels = data
    frozen = lambda h: _frozen(h, w, labels, chunk_size=5)
    recompute = lambda h: fused_cross_entropy(
        h, jax.lax.stop_gradient(w), labels, chunk_size=5)
    assert _products_over_the_head(jax.value_and_grad(frozen), hidden) == 2
    assert _products_over_the_head(jax.value_and_grad(recompute), hidden) == 3
    assert _products_over_the_head(frozen, hidden) == 1
    assert _products_over_the_head(recompute, hidden) == 1


def test_loss_fused_says_which_form_a_step_was_built_with(data, telemetry):
    hidden, w, labels = data

    def forms():
        return {c["labels"]["form"]: (c["value"], c["labels"])
                for c in telemetry.metrics().snapshot()["counters"]
                if c["name"] == "loss.fused"}

    step = jax.jit(jax.value_and_grad(
        lambda h: _frozen(h, w, labels, chunk_size=5)))
    step(hidden)
    step(hidden)                             # cached: traces nothing
    (value, labels_), = forms().values()
    assert value == 1
    assert labels_ == {"form": "one_pass", "tokens": str(B * S),
                       "vocab": str(V), "chunk": "5", "chunks": "3"}
    jax.jit(jax.grad(lambda h: fused_cross_entropy(
        h, w, labels, chunk_size=64)))(hidden)
    value, labels_ = forms()["recompute"]
    assert value == 1 and (labels_["chunk"], labels_["chunks"]) == ("12", "1")
    assert forms()["one_pass"][0] == 1


def test_fused_ce_under_pjit_mesh(data):
    """The bench/flagship path: fused CE inside a jitted step over a
    ('data','model') mesh, batch sharded on data AND the unembed head
    sharded over model (Megatron vocab split, the lm_head rule in
    TRANSFORMER_RULES) — GSPMD must partition the chunk scan without
    changing values or gradients."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    hidden, w, labels = data
    # batch of 2 -> 4 rows so data=4 divides it; vocab 37 -> 38 (one
    # large-negative pad column, used by BOTH paths) so model=2
    # divides the vocab axis
    hidden4 = jnp.concatenate([hidden, hidden], axis=0)
    labels4 = jnp.concatenate([labels, labels], axis=0)
    w38 = jnp.concatenate([w, jnp.full((w.shape[0], 1), -30.0)], axis=1)
    mesh = make_mesh(MeshSpec(data=4, model=2))
    ref = float(_reference(hidden4, w38, labels4))

    def loss(h, w_, l):
        return fused_cross_entropy(h, w_, l, chunk_size=5)

    with mesh:
        sharded = shard_batch({"h": hidden4, "l": labels4}, mesh)
        w_tp = jax.device_put(
            w38, NamedSharding(mesh, P(None, "model"))
        )
        got, grads = jax.jit(jax.value_and_grad(loss, argnums=1))(
            sharded["h"], w_tp, sharded["l"]
        )
    np.testing.assert_allclose(float(got), ref, rtol=1e-6)
    g_ref = jax.grad(_reference, argnums=1)(hidden4, w38, labels4)
    np.testing.assert_allclose(np.asarray(grads), np.asarray(g_ref),
                               atol=1e-6)


def test_llama_return_hidden_path_matches_logits_path(data):
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model = Llama(cfg)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 8)),
        jnp.int32,
    )
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    targets = jnp.roll(tokens, -1, axis=1)

    ref = cross_entropy_loss(
        model.apply({"params": params}, tokens), targets
    )
    hidden = model.apply({"params": params}, tokens, return_hidden=True)
    got = fused_cross_entropy(
        hidden.astype(jnp.float32),
        params["lm_head"]["kernel"].astype(jnp.float32),
        targets, chunk_size=4,
    )
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
