"""The decoder that mixes window and full attention layers (pattern
letters ``S``, ``F``, then ``D`` or ``G``: Trinity-Mini's ``afmoe``)
against the plain reference: logits, loss, adapter gradients, the
mixer alone, the window's mask, the sandwich norms and the embedding's
scale."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference_afmoe
from sparkdl_tpu import observe
from sparkdl_tpu.models import HybridConfig, HybridDecoder, lora_mask
from sparkdl_tpu.models.mixed_attention import MixedAttention
from sparkdl_tpu.parallel.train import cross_entropy_loss, make_lm_loss_fn

# the published key names, at a size the CPU runs in a second: six
# published layers, two of them dense, one whole period after them; the
# window (12) is under the sequence (40); heads x head size (64) is
# not the hidden size (48)
LAYER_TYPES = ["sliding_attention"] * 3 + ["full_attention"]
PUBLISHED = {
    "model_type": "afmoe", "vocab_size": 256, "hidden_size": 48,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 12, "rope_theta": 10000, "intermediate_size": 96,
    "num_experts": 16, "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "route_scale": 2.826, "rms_norm_eps": 1e-5,
    "num_dense_layers": 2, "num_hidden_layers": 6, "mup_enabled": True,
    "layer_types": (LAYER_TYPES * 2)[:6]}
TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj")
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

    def lively(path, x):
        """Adapters that do something (B is zero at initialisation) and
        norms that are not the identity's."""
        path = jax.tree_util.keystr(path)
        if "lora_b" in path:
            return x + 0.01
        if "norm" in path:
            return x + 0.1 * jax.random.normal(
                jax.random.PRNGKey(len(path)), x.shape)
        return x

    return model, jax.tree_util.tree_map_with_path(lively, params), tokens


def test_decoder_agrees_with_the_reference_in_loss_and_adapter_gradients():
    """Pattern ``SDSDSGFGSGSG``: both dense layers, one whole period of
    expert layers and two window layers more; every letter, both layer
    kinds, a window under the sequence."""
    cfg = config(lora_rank=4)
    assert cfg.pattern == "SDSDSGFGSGSG" and cfg.shared_d_ff == 32
    assert cfg.post_norm and cfg.scale_embedding
    model, params, tokens = build(cfg)
    targets = jnp.roll(tokens, -1, 1)
    arch = reference_afmoe.arch_of(PUBLISHED, 16.0, 4)

    @jax.jit
    def program(p):
        return jax.value_and_grad(lambda p: cross_entropy_loss(
            model.apply({"params": p}, tokens), targets))(p)

    with HIGHEST:
        logits = jax.jit(model.apply)({"params": params}, tokens)
        want_loss, grads = program(params)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(
        reference_afmoe.logits(params, tokens, arch), logits, atol=5e-5)
    norm = np.sqrt(sum(
        float(jnp.sum(g * g))
        for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]
        if "lora_" in jax.tree_util.keystr(p)))
    loss, got_norm, picks = reference_afmoe.loss_and_adapter_grad_norm(
        params, tokens, targets, arch)
    assert loss == pytest.approx(float(want_loss), rel=1e-5)
    assert got_norm == pytest.approx(norm, rel=1e-4) and norm > 0
    assert sorted(picks) == [5, 7, 9, 11] and picks[5].shape == (2, 40, 3)
    # the control: projections rounded to float8 move both numbers
    low = reference_afmoe.loss_and_adapter_grad_norm(
        params, tokens, targets,
        reference_afmoe.arch_of(PUBLISHED, 16.0, 4, round_to="float8_e4m3fn"))
    assert abs(low[0] - loss) / loss > 1e-3
    assert abs(low[1] - got_norm) / got_norm > 5e-2
    # adapters reach the four target projections of every mixer, by
    # name, and NOT the gate (the dense MLP's gate has the same name)
    adapted = {jax.tree_util.keystr(p) for p, m in
               jax.tree_util.tree_flatten_with_path(lora_mask(params))[0] if m}
    assert adapted == {
        f"['layer_{i}']['attn']['{name}']['lora_{ab}']"
        for i in range(0, 12, 2) for name in TARGETS for ab in "ab"}
    assert params["layer_0"]["attn"]["gate_proj"]["kernel"].shape == (48, 64)
    assert params["layer_0"]["attn"]["q_norm"]["scale"].shape == (16,)
    assert sorted(params["layer_5"]) == ["moe", "norm", "post_norm"]


def test_a_wrong_window_kind_or_scale_is_seen_by_the_comparison():
    """What the mechanisms are worth: the reference without the window,
    with every layer a window layer, or without the embedding's scale
    disagrees with the program by far more than the tolerance."""
    model, params, tokens = build(config(lora_rank=4))
    with HIGHEST:
        logits = jax.jit(model.apply)({"params": params}, tokens)
    for wrong in ({"sliding_window": 40},
                  {"layer_types": ["sliding_attention"] * 6},
                  {"layer_types": ["full_attention"] * 6},
                  {"mup_enabled": False}):
        arch = reference_afmoe.arch_of({**PUBLISHED, **wrong}, 16.0, 4)
        off = np.abs(reference_afmoe.logits(params, tokens, arch) - logits)
        assert off.max() > 1e-2, wrong


def test_decoder_takes_the_loss_functions_and_remat_as_llama_does():
    model, params, tokens = build(config(lora_rank=4))
    batch = {"inputs": tokens, "targets": jnp.roll(tokens, -1, 1)}
    with HIGHEST:
        plain = jax.jit(make_lm_loss_fn(model, loss="logits"))(params, batch)
        fused = jax.jit(make_lm_loss_fn(model, loss="fused", chunk=16))(
            params, batch)
        again = jax.jit(make_lm_loss_fn(HybridDecoder(dataclasses.replace(
            model.cfg, remat=True, attention="flash")), loss="logits"))(
                params, batch)
    assert float(fused) == pytest.approx(float(plain), rel=1e-5)
    assert float(again) == pytest.approx(float(plain), rel=1e-6)
    _, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, return_hidden=True,
        mutable=["intermediates"]))(params)
    assert sorted(sown["intermediates"]) == [
        "layer_11", "layer_5", "layer_7", "layer_9"]
    counts = sown["intermediates"]["layer_5"]["moe"]["expert_counts"][0]
    assert int(counts.sum()) == 2 * 40 * 3          # every pair lands here


@pytest.mark.parametrize("layers, dense", [(32, 2), (6, 2), (4, 0), (3, 3)])
def test_the_pattern_is_built_from_layer_types_and_the_dense_layers(
        layers, dense):
    kinds = (LAYER_TYPES * 8)[:layers]
    cfg = config({**PUBLISHED, "num_hidden_layers": layers,
                  "num_dense_layers": dense, "layer_types": kinds})
    assert len(cfg.pattern) == 2 * layers
    assert cfg.pattern[::2] == "".join(
        "F" if i % 4 == 3 else "S" for i in range(layers))
    assert cfg.pattern[1::2] == "D" * dense + "G" * (layers - dense)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.sliding_window, cfg.rope_theta, cfg.dense_d_ff,
            cfg.expert_d_ff, cfg.top_k, cfg.routed_scale) == (
                48, 4, 2, 16, 12, 10000, 96, 32, 3, 2.826)
    assert (cfg.n_routed_experts, cfg.experts_held) == (16, (0, 16))
    with pytest.raises(ValueError, match="layer_types has"):
        config({**PUBLISHED, "num_hidden_layers": layers + 1,
                "num_dense_layers": dense, "layer_types": kinds})


def test_the_other_families_keep_their_layer():
    """``post_norm`` and ``scale_embedding`` are off for ``nemotron_h``
    and ``glm4_moe_lite``: no second norm in their trees; heads that
    are not the hidden size stay refused for ``*``."""
    from tests.models.test_mla import PUBLISHED as GLM

    cfg = HybridConfig.from_published(GLM)
    assert not cfg.post_norm and not cfg.scale_embedding
    assert not HybridConfig().post_norm
    shapes = jax.eval_shape(
        lambda: HybridDecoder(dataclasses.replace(cfg, pattern="LD")).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sorted(shapes["layer_0"]) == ["mla", "norm"]
    with pytest.raises(ValueError, match="head_dim"):
        config(pattern="S*")
    with pytest.raises(ValueError, match="pairs"):
        config(head_dim=15)
    with pytest.raises(ValueError, match="sees itself"):
        config(sliding_window=0)
    with pytest.raises(ValueError, match="n_kv_heads"):
        config(n_kv_heads=3)


# -- the mixer alone ---------------------------------------------------------


def mixer(window, **kw):
    cfg = config(lora_rank=4, **kw)
    layer = MixedAttention(cfg, window)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 48))
    params = build_mixer(layer, x)
    return layer, params, x, dict(reference_afmoe.arch_of(PUBLISHED, 16.0, 4))


def build_mixer(layer, x):
    params = jax.jit(layer.init)(jax.random.PRNGKey(1), x)["params"]
    return jax.tree_util.tree_map_with_path(
        lambda p, a: a + 0.01 if "lora_b" in jax.tree_util.keystr(p)
        else a + 0.1 * jnp.cos(jnp.arange(a.size, dtype=a.dtype)).reshape(
            a.shape) if "norm" in jax.tree_util.keystr(p) else a, params)


@pytest.mark.parametrize("window", [None, 1, 12, 39, 40, 64], ids=str)
def test_the_mixer_alone_is_the_references(window):
    """A full layer and windows of one key, under, at and past the
    sequence: output and gradients in the weights and the input."""
    layer, params, x, arch = mixer(window)
    want_fn = functools.partial(
        reference_afmoe._attention, window=window, arch=arch)
    with HIGHEST:
        got = jax.jit(layer.apply)({"params": params}, x)
        want = jax.jit(want_fn)(params, x)
        g_got = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
            layer.apply({"params": p}, x))), argnums=(0, 1)))(params, x)
        g_want = jax.jit(jax.grad(lambda p, x: jnp.sum(jnp.sin(
            want_fn(p, x))), argnums=(0, 1)))(params, x)
    np.testing.assert_allclose(got, want, atol=2e-5)
    jax.tree.map(functools.partial(np.testing.assert_allclose, atol=5e-5),
                 g_got, g_want)
    # a token's output moves with nothing after it, and in a window
    # layer with nothing `window` or more before it
    later = x.at[:, 20:].add(1.0)
    with HIGHEST:
        moved = jax.jit(layer.apply)({"params": params}, later)
        early = jax.jit(layer.apply)({"params": params},
                                     x.at[:, :8].add(1.0))
    np.testing.assert_allclose(moved[:, :20], got[:, :20], atol=1e-6)
    assert float(jnp.abs(moved[:, 20:] - got[:, 20:]).max()) > 1e-3
    seen_until = 40 if window is None else min(40, 7 + window)
    np.testing.assert_allclose(
        early[:, seen_until:], got[:, seen_until:], atol=1e-6)
    assert float(jnp.abs(early[:, :seen_until] - got[:, :seen_until]
                         ).max()) > 1e-3


def test_rope_turns_the_window_layers_and_no_other():
    """A full layer takes no positions: it gives a permuted prefix the
    permuted answer at the last token; a window layer does not."""
    full, params, x, _ = mixer(None)
    window, _, _, _ = mixer(40)
    swapped = x.at[:, 3].set(x[:, 17]).at[:, 17].set(x[:, 3])
    with HIGHEST:
        for layer, same in ((full, True), (window, False)):
            a = jax.jit(layer.apply)({"params": params}, x)[:, -1]
            b = jax.jit(layer.apply)({"params": params}, swapped)[:, -1]
            assert (float(jnp.abs(a - b).max()) < 1e-5) == same


def test_the_flash_path_hands_the_window_to_the_kernels(monkeypatch):
    """``attention="flash"``: the mixer calls ``flash_attention`` with
    its window (None for a full layer), and the kernels, interpreted,
    give the mixer's answer."""
    from sparkdl_tpu.ops import attention

    seen = []
    real = attention.flash_attention

    def spy(q, k, v, *, causal, window):
        seen.append((q.shape, k.shape, causal, window))
        return real(q, k, v, causal=causal, window=window, interpret=True)

    monkeypatch.setattr(attention, "flash_attention", spy)
    for window in (12, None):
        plain, params, x, _ = mixer(window)
        flash, _, _, _ = mixer(window, attention="flash")
        with HIGHEST:
            np.testing.assert_allclose(
                jax.jit(flash.apply)({"params": params}, x),
                jax.jit(plain.apply)({"params": params}, x), atol=2e-5)
    # (init traces the mixer too): keys and values at the query heads
    assert set(seen) == {((2, 40, 4, 16), (2, 40, 4, 16), True, 12),
                         ((2, 40, 4, 16), (2, 40, 4, 16), True, None)}


def test_mixed_attention_is_counted_once_a_traced_mixer(
        monkeypatch, tmp_path):
    model, params, tokens = build(config())     # traced before the count
    monkeypatch.setenv(observe.TELEMETRY_DIR_ENV, str(tmp_path))
    observe._reset_for_tests()
    try:
        jax.jit(lambda p: model.apply({"params": p}, tokens[:1, :10]))(params)
        counters = observe.metrics().snapshot()["counters"]
    finally:
        observe._reset_for_tests()
    mixed = {c["labels"]["window"]: c for c in counters
             if c["name"] == "attn.mixed"}
    assert sorted(mixed) == ["0", "12"]
    assert mixed["12"]["value"] == 5 and mixed["0"]["value"] == 1
    assert mixed["12"]["labels"] == {
        "heads": "4", "kv_heads": "2", "head_dim": "16", "window": "12",
        "rope": "True", "gate": "True", "qk_norm": "True"}
    assert mixed["0"]["labels"]["rope"] == "False"
