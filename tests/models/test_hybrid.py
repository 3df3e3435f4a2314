"""The patterned decoder (Mamba-2 / latent experts / attention) against
the plain reference, and the expert layer's share against the whole."""

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_hybrid
from sparkdl_tpu import observe
from sparkdl_tpu.models import HybridConfig, HybridDecoder, lora_mask, moe
from sparkdl_tpu.models.moe import LatentMoE, latent_experts, route_sigmoid
from sparkdl_tpu.parallel.train import cross_entropy_loss, make_lm_loss_fn

# the published key names, at a size the CPU runs in a second
PUBLISHED = {
    "hybrid_override_pattern": "ME*E", "hidden_size": 64, "vocab_size": 256,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "n_routed_experts": 16, "num_experts_per_tok": 3, "moe_latent_size": 32,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "routed_scaling_factor": 2.5, "norm_eps": 1e-5}
HIGHEST = jax.default_matmul_precision("highest")


SHARE = dict(lora_rank=4, n_routed_experts=32, experts_held=(8, 16))


def config(**kw):
    return HybridConfig.from_published(
        PUBLISHED, **{"dtype": jnp.float32, **kw})


@functools.lru_cache(maxsize=None)
def build(cfg, shape=(2, 40), seed=0):
    model = HybridDecoder(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                                cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed + 1), tokens)["params"]
    # adapters that do something: B is zero at initialisation
    params = jax.tree_util.tree_map_with_path(
        lambda p, x: x + 0.01 if "lora_b" in jax.tree_util.keystr(p) else x,
        params)
    return model, params, tokens


def test_decoder_agrees_with_the_reference_in_loss_and_adapter_gradients():
    """All three letters, a share of the experts that does not start at
    expert 0, a sequence that is no multiple of the chunk."""
    model, params, tokens = build(config(**SHARE))
    targets = jnp.roll(tokens, -1, 1)
    arch = reference_hybrid.arch_of(PUBLISHED, 16.0, 4, first_expert=8)

    @jax.jit
    def program(p):
        return jax.value_and_grad(lambda p: cross_entropy_loss(
            model.apply({"params": p}, tokens), targets))(p)

    with HIGHEST:
        logits = jax.jit(model.apply)({"params": params}, tokens)
        want_loss, grads = program(params)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(
        reference_hybrid.logits(params, tokens, arch), logits, atol=2e-5)
    norm = np.sqrt(sum(
        float(jnp.sum(g * g))
        for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]
        if "lora_" in jax.tree_util.keystr(p)))
    loss, got_norm, picks = reference_hybrid.loss_and_adapter_grad_norm(
        params, tokens, targets, arch)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert got_norm == pytest.approx(norm, rel=1e-4) and norm > 0
    assert sorted(picks) == [1, 3] and picks[1].shape == (2, 40, 3)
    # adapters reach the state-space projections and attention, by name
    adapted = {jax.tree_util.keystr(p) for p, m in
               jax.tree_util.tree_flatten_with_path(lora_mask(params))[0] if m}
    assert {"['layer_0']['mamba']['in_proj']['lora_a']",
            "['layer_0']['mamba']['out_proj']['lora_b']",
            "['layer_2']['attn']['q_proj']['lora_a']",
            "['layer_2']['attn']['v_proj']['lora_b']"} <= adapted


def test_decoder_takes_the_loss_functions_and_remat_as_llama_does():
    """``make_lm_loss_fn``'s two paths give one loss; ``remat`` changes
    no number; what the layers sow is read as ``router_probs`` is."""
    model, params, tokens = build(config(**SHARE))
    batch = {"inputs": tokens, "targets": jnp.roll(tokens, -1, 1)}
    with HIGHEST:
        plain = jax.jit(make_lm_loss_fn(model, loss="logits"))(params, batch)
        fused = jax.jit(make_lm_loss_fn(model, loss="fused", chunk=16))(
            params, batch)
        again = jax.jit(make_lm_loss_fn(HybridDecoder(dataclasses.replace(
            model.cfg, remat=True)), loss="logits"))(params, batch)
    assert float(fused) == pytest.approx(float(plain), rel=1e-5)
    assert float(again) == pytest.approx(float(plain), rel=1e-6)
    _, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, return_hidden=True,
        mutable=["intermediates"]))(params)
    sown = sown["intermediates"]["layer_1"]["moe"]
    counts, picks = sown["expert_counts"][0], sown["picks"][0]
    assert counts.shape == (16,) and picks.shape == (2 * 40, 3)
    assert int(counts.sum()) == int(((picks >= 8) & (picks < 24)).sum()) > 0


def test_vocabulary_slice_ids_logits_and_loss_are_over_the_slice():
    """A sliced vocabulary is a smaller vocabulary: embedding and head
    have the slice's rows, and the loss is the cross-entropy over them."""
    cfg = config(vocab_size=64)
    model, params, tokens = build(cfg)
    assert params["embed"]["embedding"].shape == (64, 64)
    assert params["lm_head"]["kernel"].shape == (64, 64)
    assert int(tokens.max()) < 64
    targets = jnp.roll(tokens, -1, 1)
    logits = jax.jit(model.apply)({"params": params}, tokens)
    assert logits.shape[-1] == 64
    want = -jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), targets[..., None], -1).mean()
    loss = jax.jit(make_lm_loss_fn(model, loss="fused", chunk=16))(
        params, {"inputs": tokens, "targets": targets})
    assert float(loss) == pytest.approx(float(want), rel=1e-5)


def test_pattern_and_share_are_checked():
    with pytest.raises(ValueError, match="pattern"):
        config(pattern="MXE")
    with pytest.raises(ValueError, match="experts_held"):
        config(n_routed_experts=16, experts_held=(8, 16))
    with pytest.raises(ValueError, match="head_dim"):
        config(head_dim=32)
    assert config().attn.n_kv_heads == 2 and config().experts_held == (0, 16)


# -- the expert layer --------------------------------------------------------


def test_route_sigmoid_picks_by_score_plus_bias_and_weights_by_score():
    logits = jnp.log(jnp.array([[0.9, 0.8, 0.6, 0.5]]) / (
        1 - jnp.array([[0.9, 0.8, 0.6, 0.5]])))        # sigmoid gives these
    bias = jnp.array([0.0, -0.5, 0.0, 0.3])             # 0.9, 0.3, 0.6, 0.8
    idx, weights = route_sigmoid(logits, bias, 2, scale=5.0)
    assert idx.tolist() == [[0, 3]]                     # not expert 1
    np.testing.assert_allclose(                         # by s, not s + b
        weights, [[5 * 0.9 / 1.4, 5 * 0.5 / 1.4]], rtol=1e-6)
    idx, _ = route_sigmoid(logits, jnp.zeros(4), 2)
    assert idx.tolist() == [[0, 1]]


def dense_masked(v, idx, weights, w_up, w_down, held):
    """Every expert held on every token, its weight zero where it was
    not chosen: the plain way to write the layer."""
    out = jnp.zeros_like(v)
    for e in range(held[1]):
        weight = jnp.where(idx == held[0] + e, weights, 0.0).sum(
            -1, keepdims=True)
        out = out + weight * (jnp.square(jax.nn.relu(v @ w_up[e])) @ w_down[e])
    return out


def routed(held, bias, latent, tokens=96, k=3, d_ff=48, n_experts=16):
    """(v, weights, idx, w_up, w_down) of a layer of `tokens` that holds
    `held`, its router biased by `bias` (expert -> added score)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    v = jax.random.normal(keys[0], (tokens, latent))
    # (the hidden layer as large at any width as at 32)
    w_up = jax.random.normal(keys[1], (held[1], latent, d_ff)) * (
        0.2 * (32 / latent) ** 0.5)
    w_down = jax.random.normal(keys[2], (held[1], d_ff, latent)) * 0.2
    scores = jnp.zeros(n_experts)
    for expert, add in bias.items():
        scores = scores.at[expert].set(add)
    idx, weights = route_sigmoid(
        jax.random.normal(keys[3], (tokens, n_experts)), scores, k, scale=2.5)
    return v, weights, idx, w_up, w_down


def out_and_gradients(layer, v, weights):
    """A layer's output and the gradients in `v` and `weights` of a
    loss that weighs every element differently."""
    out, counts = jax.jit(layer)(v, weights)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a)[0])),
                             argnums=(0, 1)))(v, weights)
    return out, grads, counts


# tokens 96 x picks 3 = 288 rows of the sorted buffer, in tiles of 32
DISPATCH = {
    # one expert held takes nearly every token and another none
    "heavy imbalance": dict(held=(4, 8), bias={6: 10.0, 9: -10.0},
                            latent=128, path="pallas"),
    "every pair held": dict(held=(0, 16), bias={}, latent=128, path="pallas"),
    "no pair held": dict(held=(12, 4), bias={e: -10.0 for e in range(12, 16)},
                         latent=128, path="pallas"),
    "n off the tile": dict(held=(2, 5), bias={}, latent=256, path="pallas"),
    "width off the tiling": dict(held=(4, 8), bias={6: 10.0}, latent=32,
                                 path="jnp"),
}


@pytest.mark.parametrize("case", DISPATCH)
def test_sparse_dispatch_is_the_dense_layer_under_heavy_imbalance(
        case, monkeypatch):
    """Whatever the routing sends here, all of it, none of it, or nearly
    all of it to one expert, no pair is dropped, forward or backward:
    the rows' kernels (interpreted), the plain gathers and every expert
    held on every token give one layer. A width the kernels do not take
    falls to the plain gathers."""
    from sparkdl_tpu.ops.pallas import moe_rows

    monkeypatch.setattr(moe_rows, "ROWS_TILE", 32)
    held, bias, latent, path = (DISPATCH[case][k] for k in (
        "held", "bias", "latent", "path"))
    v, weights, idx, w_up, w_down = routed(held, bias, latent)
    tokens, k = idx.shape
    assert moe.dispatch_path(tokens, latent, interpret=True) == path
    assert moe.dispatch_path(tokens, latent) == "jnp"        # off the TPU

    def sparse(interpret):
        return lambda v, weights: latent_experts(
            v, idx, weights, w_up, w_down, held, interpret=interpret)

    with HIGHEST:
        out, got_g, counts = out_and_gradients(sparse(True), v, weights)
        plain, plain_g, plain_counts = out_and_gradients(
            sparse(None), v, weights)
        want, want_g, _ = out_and_gradients(
            lambda v, weights: (dense_masked(
                v, idx, weights, w_up, w_down, held), None), v, weights)
    here = (idx >= held[0]) & (idx < held[0] + held[1])
    n = int(counts.sum())
    assert n == int(here.sum()) and (counts == plain_counts).all()
    if case == "heavy imbalance":
        assert counts[6 - 4] == tokens and counts[9 - 4] == 0
        assert 0 < n < tokens * k
    elif case == "every pair held":
        assert n == tokens * k and n % 32 == 0       # every tile live
    elif case == "no pair held":
        assert n == 0
    elif case == "n off the tile":
        assert n % 32 and 32 < n < tokens * k - 32
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(plain, want, atol=2e-5)
    for g, p, w in zip(got_g, plain_g, want_g):
        # (a sum over the width that nearly cancels keeps the rounding
        # of its terms, which are as large as the largest gradient)
        atol = 2e-5 + 2e-6 * float(np.abs(w).max())
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=atol)
        np.testing.assert_allclose(p, w, rtol=1e-4, atol=atol)


@jax.custom_vjp
def nan_past(x, n):
    """`x` with NaN in every row from `n` on, its cotangent too."""
    return jnp.where(jnp.arange(x.shape[0])[:, None] < n, x, jnp.nan)


nan_past.defvjp(lambda x, n: (nan_past(x, n), n),
                lambda n, g: (nan_past(g, n), None))


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["pallas", "jnp"])
def test_what_the_sorted_buffer_holds_past_the_pairs_is_never_used(
        interpret, monkeypatch):
    """The grouped products compute no row past ``counts.sum()``: with
    NaN there, in what the second product gives and in what comes back
    from the first, the layer's output and gradients are what they
    were."""
    from sparkdl_tpu.ops.grouped_matmul import grouped_matmul
    from sparkdl_tpu.ops.pallas import moe_rows

    monkeypatch.setattr(moe_rows, "ROWS_TILE", 32)
    held, latent = (4, 8), 128
    v, weights, idx, w_up, w_down = routed(held, {6: 10.0, 9: -10.0}, latent)

    def layer(v, weights):
        return latent_experts(v, idx, weights, w_up, w_down, held,
                              interpret=interpret)

    def poisoned(lhs, rhs, counts):
        """The product with NaN past the pairs in its result, and (the
        first product, which takes the rows) in what it hands back."""
        n = counts.sum()
        if lhs.shape[1] == latent:
            lhs = nan_past(lhs, n)
        return nan_past(grouped_matmul(lhs, rhs, counts), n)

    with HIGHEST:
        clean, clean_g, counts = out_and_gradients(layer, v, weights)
        monkeypatch.setattr(moe, "grouped_matmul", poisoned)
        got, got_g, _ = out_and_gradients(layer, v, weights)
    assert 0 < int(counts.sum()) < idx.size
    for a, b in zip((got, *got_g), (clean, *clean_g)):
        assert np.isfinite(a).all()
        np.testing.assert_array_equal(a, b)


def test_four_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Each share holds a quarter of the experts and routes over all of
    them; the shared expert, which every chip computes alike, is counted
    once."""
    cfg = config()
    whole = LatentMoE(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64))
    params = jax.jit(whole.init)(jax.random.PRNGKey(1), x)["params"]
    with HIGHEST:
        shared = jnp.square(jax.nn.relu(
            x @ params["shared_up"]["kernel"])) @ params[
                "shared_down"]["kernel"]
        total = -3 * shared
        for first in (0, 4, 8, 12):
            share = {**params, "w_up": params["w_up"][first:first + 4],
                     "w_down": params["w_down"][first:first + 4]}
            total = total + jax.jit(LatentMoE(dataclasses.replace(
                cfg, experts_held=(first, 4))).apply)({"params": share}, x)
        arch = dict(reference_hybrid.arch_of(PUBLISHED))
        want = jax.jit(functools.partial(
            reference_hybrid._experts, arch=arch))(params, x)
        uncut = jax.jit(whole.apply)({"params": params}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_allclose(uncut, want, atol=2e-5)


def test_dispatch_is_counted_once_a_traced_layer(monkeypatch, tmp_path):
    """``moe.dispatch`` says what share a step was built with, as
    ``flash.tiles`` says which tiles: once a layer when it is traced."""
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    try:
        cfg = config(n_routed_experts=32, experts_held=(8, 16))
        x = jnp.zeros((1, 10, 64))
        layer = LatentMoE(cfg)
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]
        before = observe.metrics().snapshot()["counters"]
        step = jax.jit(lambda p: layer.apply({"params": p}, x))
        step(params)
        step(params)                         # cached: traces nothing
        counted = [c for c in observe.metrics().snapshot()["counters"]
                   if c["name"] == "moe.dispatch"]
    finally:
        observe._reset_for_tests()
    assert len(counted) == 1
    # the initialisation traced the layer once, the step once more
    assert counted[0]["value"] == 1 + sum(
        c["value"] for c in before if c["name"] == "moe.dispatch") == 2
    assert counted[0]["labels"] == {
        "held": "16", "of": "32", "picks": "3", "rows": "30",
        "product": "gmm", "form": "relu2", "path": "jnp"}
