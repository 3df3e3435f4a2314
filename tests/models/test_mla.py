"""The latent-attention expert decoder (pattern letters ``L``, ``D``,
``G``: GLM-4.7-Flash's ``glm4_moe_lite``) against the plain reference,
the shared rope key, and the gated expert layer's dispatch and shares
against the whole."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_mla
from sparkdl_tpu import observe
from sparkdl_tpu.models import HybridConfig, HybridDecoder, lora_mask, moe
from sparkdl_tpu.models.llama import apply_rope, rope_freqs
from sparkdl_tpu.models.mla import LatentAttention
from sparkdl_tpu.models.moe import GatedMoE, route_sigmoid, sorted_experts
from sparkdl_tpu.parallel.train import cross_entropy_loss, make_lm_loss_fn

# the published key names, at a size the CPU runs in a second: three
# published layers, the first dense
PUBLISHED = {
    "model_type": "glm4_moe_lite", "vocab_size": 256, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 32, "qk_nope_head_dim": 24, "qk_rope_head_dim": 8,
    "v_head_dim": 32, "rope_theta": 1e6, "intermediate_size": 128,
    "num_experts_per_tok": 3, "moe_intermediate_size": 48,
    "routed_scaling_factor": 1.8, "rms_norm_eps": 1e-5,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_hidden_layers": 3,
    "first_k_dense_replace": 1}
TARGETS = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")
HIGHEST = jax.default_matmul_precision("highest")


def config(published=PUBLISHED, **kw):
    return HybridConfig.from_published(
        published, **{"dtype": jnp.float32, "lora_targets": TARGETS, **kw})


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
    """Pattern ``LDLGLG``: latent attention, the leading dense MLP and
    two gated expert layers, adapters on all five projections."""
    cfg = config(lora_rank=4)
    assert cfg.pattern == "LDLGLG" and cfg.shared_d_ff == 48
    model, params, tokens = build(cfg)
    targets = jnp.roll(tokens, -1, 1)
    arch = reference_mla.arch_of(PUBLISHED, 16.0, 4)

    @jax.jit
    def program(p):
        return jax.value_and_grad(lambda p: cross_entropy_loss(
            model.apply({"params": p}, tokens), targets))(p)

    with HIGHEST:
        logits = jax.jit(model.apply)({"params": params}, tokens)
        want_loss, grads = program(params)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(
        reference_mla.logits(params, tokens, arch), logits, atol=2e-5)
    norm = np.sqrt(sum(
        float(jnp.sum(g * g))
        for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]
        if "lora_" in jax.tree_util.keystr(p)))
    loss, got_norm, picks = reference_mla.loss_and_adapter_grad_norm(
        params, tokens, targets, arch)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert got_norm == pytest.approx(norm, rel=1e-4) and norm > 0
    assert sorted(picks) == [3, 5] and picks[3].shape == (2, 40, 3)
    # the control: projections rounded to float8 move both numbers
    low = reference_mla.loss_and_adapter_grad_norm(
        params, tokens, targets,
        reference_mla.arch_of(PUBLISHED, 16.0, 4, round_to="float8_e4m3fn"))
    assert abs(low[0] - loss) / loss > 1e-3
    assert abs(low[1] - got_norm) / got_norm > 5e-2
    # adapters reach each of the five projections of every mixer, by name
    adapted = {jax.tree_util.keystr(p) for p, m in
               jax.tree_util.tree_flatten_with_path(lora_mask(params))[0] if m}
    assert adapted == {
        f"['layer_{i}']['mla']['{name}']['lora_{ab}']"
        for i in (0, 2, 4) for name in TARGETS for ab in "ab"}


def test_decoder_takes_the_loss_functions_and_remat_as_llama_does():
    """``make_lm_loss_fn``'s two paths give one loss; ``remat`` changes
    no number; a ``G`` layer sows under ``moe`` what an ``E`` layer
    sows."""
    model, params, tokens = build(config(lora_rank=4))
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
    assert sorted(sown["intermediates"]) == ["layer_3", "layer_5"]
    sown = sown["intermediates"]["layer_3"]["moe"]
    counts, picks = sown["expert_counts"][0], sown["picks"][0]
    assert counts.shape == (16,) and picks.shape == (2 * 40, 3)
    assert int(counts.sum()) == 2 * 40 * 3          # every pair lands here


@pytest.mark.parametrize("layers, dense", [(47, 1), (7, 1), (4, 2), (2, 0)])
def test_the_pattern_is_built_from_the_depth_and_the_leading_dense_layers(
        layers, dense):
    cfg = config({**PUBLISHED, "num_hidden_layers": layers,
                  "first_k_dense_replace": dense})
    assert cfg.pattern == "LD" * dense + "LG" * (layers - dense)
    assert len(cfg.pattern) == 2 * layers
    assert (cfg.q_rank, cfg.kv_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
            cfg.v_dim, cfg.dense_d_ff, cfg.rope_theta) == (
                48, 32, 24, 8, 32, 128, 1e6)
    assert cfg.attn.d_ff == 128 and cfg.experts_held == (0, 16)


def test_a_mixers_shape_is_checked_where_the_pattern_has_its_letter():
    """20 heads of 256 are not ``d_model``: fine for ``L``, refused for
    ``*``; the state-space and share checks likewise."""
    assert config(n_heads=5).n_heads * 16 != 64               # no "*": taken
    HybridConfig(pattern="LD", ssm_heads=7, ssm_groups=2, experts_held=(9, 9))
    with pytest.raises(ValueError, match="head_dim"):
        HybridConfig(pattern="ME*", head_dim=32)
    with pytest.raises(ValueError, match="head_dim"):
        HybridConfig(pattern="L*", n_heads=5)
    with pytest.raises(ValueError, match="ssm_groups"):
        HybridConfig(pattern="LM", ssm_heads=7, ssm_groups=2)
    with pytest.raises(ValueError, match="experts_held"):
        config(n_routed_experts=16, experts_held=(8, 16))
    with pytest.raises(ValueError, match="pairs"):
        config(qk_rope_dim=7)
    with pytest.raises(ValueError, match="one head size"):
        config(v_dim=16, attention="flash")
    with pytest.raises(ValueError, match=r"\['\*', 'D', 'E', 'F', 'G', 'L', 'M', 'S'\]"):
        config(pattern="LX")


# -- latent attention --------------------------------------------------------

# (qk_nope, qk_rope, v): GLM's proportions; values as wide as the part
# without position (a swapped split of k_nope | v passes unseen there);
# values narrower than a head's scores
WIDTHS = [(24, 8, 32), (16, 16, 16), (8, 24, 16)]


def attention(widths, **kw):
    nope, rope, v = widths
    cfg = config(qk_nope_dim=nope, qk_rope_dim=rope, v_dim=v, lora_rank=4,
                 **kw)
    layer = LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a + 0.01 if "lora_b" in jax.tree_util.keystr(p) else a,
        params)
    arch = dict(reference_mla.arch_of({
        **PUBLISHED, "qk_nope_head_dim": nope, "qk_rope_head_dim": rope,
        "v_head_dim": v}, 16.0, 4))
    return layer, params, x, arch


@pytest.mark.parametrize("widths", WIDTHS, ids=str)
def test_latent_attention_alone_is_the_references(widths):
    layer, params, x, arch = attention(widths)
    with HIGHEST:
        got = jax.jit(layer.apply)({"params": params}, x)
        want = jax.jit(functools.partial(
            reference_mla._attention, arch=arch))(params, x)
        g_got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
            layer.apply({"params": p}, x))), argnums=(0, 1)))(params, x)
        g_want = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
            reference_mla._attention(p, x, arch=arch))),
            argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    jax.tree.map(functools.partial(np.testing.assert_allclose, atol=5e-5),
                 g_got, g_want)
    # causal: a token's output does not move with what follows it
    later = x.at[:, 20:].add(1.0)
    with HIGHEST:
        moved = jax.jit(layer.apply)({"params": params}, later)
    np.testing.assert_allclose(moved[:, :20], got[:, :20], atol=1e-6)
    assert float(jnp.abs(moved[:, 20:] - got[:, 20:]).max()) > 1e-3


def test_the_flash_kernels_take_a_head_of_scores_and_values_alike():
    """``attention="flash"`` (the kernels interpreted off the TPU are
    ``ops.attention``'s reference path) gives the same mixer."""
    layer, params, x, _ = attention(WIDTHS[0])
    flash, _, _, _ = attention(WIDTHS[0], attention="flash")
    with HIGHEST:
        np.testing.assert_allclose(
            jax.jit(flash.apply)({"params": params}, x),
            jax.jit(layer.apply)({"params": params}, x), atol=2e-5)


def test_the_shared_rope_key_is_turned_once_for_every_head():
    """One ``k_rope`` a token: turning it before the broadcast over the
    heads is turning each head's copy after it, and every head of the
    mixer's keys carries the same turned vector."""
    b, s, heads, rope = 2, 40, 4, 8
    k_rope = jax.random.normal(jax.random.PRNGKey(0), (b, s, rope))
    cos, sin = rope_freqs(rope, s, 1e6)
    positions = jnp.arange(s)
    once = jnp.broadcast_to(apply_rope(
        k_rope[:, :, None, :], cos, sin, positions), (b, s, heads, rope))
    a_head = apply_rope(jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, heads, rope)), cos, sin, positions)
    np.testing.assert_array_equal(once, a_head)
    assert float(jnp.abs(once[:, 1:] - k_rope[:, 1:, None, :]).max()) > 0.1
    np.testing.assert_array_equal(once[:, 0], jnp.broadcast_to(
        k_rope[:, 0, None, :], (b, heads, rope)))    # position 0 turns nothing
    # in the mixer: the key is read from the LAST qk_rope columns of
    # kv_a_proj_with_mqa, whatever the head
    layer, params, x, arch = attention(WIDTHS[0])
    zeroed = jax.tree.map(lambda a: a, params)
    kernel = zeroed["kv_a_proj_with_mqa"]["kernel"]
    zeroed["kv_a_proj_with_mqa"]["kernel"] = kernel.at[:, -8:].set(0.0)
    with HIGHEST:
        got = jax.jit(layer.apply)({"params": zeroed}, x)
        want = jax.jit(functools.partial(
            reference_mla._attention, arch=arch))(zeroed, x)
        whole = jax.jit(layer.apply)({"params": params}, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert float(jnp.abs(whole - got).max()) > 1e-3


def test_latent_attention_is_counted_once_a_traced_mixer(
        monkeypatch, tmp_path):
    """``mla.attention`` says which shapes a step was built with, and a
    ``G`` layer's ``moe.dispatch`` its form and path."""
    model, params, tokens = build(config())     # traced before the count
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    try:
        jax.jit(lambda p: model.apply({"params": p}, tokens[:1, :10]))(params)
        counters = observe.metrics().snapshot()["counters"]
    finally:
        observe._reset_for_tests()
    mla = [c for c in counters if c["name"] == "mla.attention"]
    assert len(mla) == 1 and mla[0]["value"] == 3        # three mixers
    assert mla[0]["labels"] == {
        "heads": "4", "qk_nope": "24", "qk_rope": "8", "v": "32",
        "q_rank": "48", "kv_rank": "32", "form": "expanded"}
    dispatch = [c for c in counters if c["name"] == "moe.dispatch"]
    assert len(dispatch) == 1 and dispatch[0]["value"] == 2
    assert dispatch[0]["labels"] == {
        "held": "16", "of": "16", "picks": "3", "rows": "30",
        "product": "gmm", "form": "gated", "path": "jnp"}


# -- the gated expert layer --------------------------------------------------


def dense_masked(v, idx, weights, w_gate_up, w_down, held):
    """Every expert held on every token, its weight zero where it was
    not chosen: the plain way to write the layer."""
    d_ff = w_down.shape[1]
    out = jnp.zeros_like(v)
    for e in range(held[1]):
        weight = jnp.where(idx == held[0] + e, weights, 0.0).sum(
            -1, keepdims=True)
        gate, up = v @ w_gate_up[e, :, :d_ff], v @ w_gate_up[e, :, d_ff:]
        out = out + weight * ((jax.nn.silu(gate) * up) @ w_down[e])
    return out


def routed(held, bias, width, tokens=96, k=3, d_ff=48, n_experts=16):
    """(v, weights, idx, w_gate_up, w_down) of a layer of `tokens` that
    holds `held`, its router biased by `bias` (expert -> added score)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    v = jax.random.normal(keys[0], (tokens, width))
    w_gate_up = jax.random.normal(keys[1], (held[1], width, 2 * d_ff)) * (
        0.2 * (32 / width) ** 0.5)
    w_down = jax.random.normal(keys[2], (held[1], d_ff, width)) * 0.2
    scores = jnp.zeros(n_experts)
    for expert, add in bias.items():
        scores = scores.at[expert].set(add)
    idx, weights = route_sigmoid(
        jax.random.normal(keys[3], (tokens, n_experts)), scores, k, scale=1.8)
    return v, weights, idx, w_gate_up, w_down


def out_and_gradients(layer, v, weights):
    """A layer's output and the gradients in `v` and `weights` of a
    loss that weighs every element differently."""
    out, counts = jax.jit(layer)(v, weights)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(layer(*a)[0])),
                             argnums=(0, 1)))(v, weights)
    return out, grads, counts


# tokens 96 x picks 3 = 288 rows of the sorted buffer, in tiles of 32
DISPATCH = {
    # one expert takes nearly every token and another none
    "heavy imbalance": dict(held=(0, 16), bias={6: 10.0, 9: -10.0}),
    "every pair held": dict(held=(0, 16), bias={}),
    "a share, heavy imbalance": dict(held=(4, 8), bias={6: 10.0, 9: -10.0}),
    "no pair held": dict(held=(12, 4), bias={e: -10.0 for e in range(12, 16)}),
}


@pytest.mark.parametrize("case", DISPATCH)
def test_gated_dispatch_is_the_dense_layer_under_heavy_imbalance(
        case, monkeypatch):
    """The gated form through the same sorted dispatch: the rows'
    kernels (interpreted), the plain gathers and every expert held on
    every token give one layer, forward and in both gradients, whatever
    the routing sends where."""
    from sparkdl_tpu.ops.pallas import moe_rows

    monkeypatch.setattr(moe_rows, "ROWS_TILE", 32)
    held, bias = DISPATCH[case]["held"], DISPATCH[case]["bias"]
    v, weights, idx, w_gate_up, w_down = routed(held, bias, width=128)
    tokens, k = idx.shape
    assert moe.dispatch_path(tokens, 128, interpret=True) == "pallas"

    def sparse(interpret):
        return lambda v, weights: sorted_experts(
            v, idx, weights, w_gate_up, w_down, held, interpret=interpret,
            form="gated")

    with HIGHEST:
        out, got_g, counts = out_and_gradients(sparse(True), v, weights)
        plain, plain_g, plain_counts = out_and_gradients(
            sparse(None), v, weights)
        want, want_g, _ = out_and_gradients(
            lambda v, weights: (dense_masked(
                v, idx, weights, w_gate_up, w_down, held), None), v, weights)
    here = (idx >= held[0]) & (idx < held[0] + held[1])
    n = int(counts.sum())
    assert n == int(here.sum()) and (counts == plain_counts).all()
    if held == (0, 16):
        assert n == tokens * k
    if "imbalance" in case:
        assert counts[6 - held[0]] == tokens and counts[9 - held[0]] == 0
    if case == "no pair held":
        assert n == 0
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(plain, want, atol=2e-5)
    for g, p, w in zip(got_g, plain_g, want_g):
        atol = 2e-5 + 2e-6 * float(np.abs(w).max())
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=atol)
        np.testing.assert_allclose(p, w, rtol=1e-4, atol=atol)


def test_an_unknown_expert_form_is_refused():
    v, weights, idx, w_gate_up, w_down = routed((0, 16), {}, width=32)
    with pytest.raises(KeyError, match="glu"):
        sorted_experts(v, idx, weights, w_gate_up, w_down, (0, 16), form="glu")


def test_four_shares_of_a_gated_layer_add_up_to_the_uncut_reference():
    """Each share holds a quarter of the experts and routes over all of
    them; the shared expert, which every chip computes alike, is counted
    once."""
    cfg = config()
    whole = GatedMoE(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 64))
    params = jax.jit(whole.init)(jax.random.PRNGKey(1), x)["params"]
    assert params["w_gate_up"].shape == (16, 64, 96)
    with HIGHEST:
        shared = (jax.nn.silu(x @ params["shared_gate"]["kernel"])
                  * (x @ params["shared_up"]["kernel"])) @ params[
                      "shared_down"]["kernel"]
        total = -3 * shared
        for first in (0, 4, 8, 12):
            share = {**params,
                     "w_gate_up": params["w_gate_up"][first:first + 4],
                     "w_down": params["w_down"][first:first + 4]}
            total = total + jax.jit(GatedMoE(dataclasses.replace(
                cfg, experts_held=(first, 4))).apply)({"params": share}, x)
        arch = dict(reference_mla.arch_of(PUBLISHED))
        want = jax.jit(functools.partial(
            reference_mla._experts, arch=arch))(params, x)
        uncut = jax.jit(whole.apply)({"params": params}, x)
    np.testing.assert_allclose(total, want, atol=2e-5)
    np.testing.assert_allclose(uncut, want, atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-2
