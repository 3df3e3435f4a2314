"""Flash-attention kernel oracle tests (interpret mode on CPU; the
same kernel runs compiled on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops.attention import flash_attention
from sparkdl_tpu.ops.pallas import flash_attention as flash_kernels
from sparkdl_tpu.parallel.ring_attention import attention_reference


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("s", [128, 256])
def test_flash_matches_reference(causal, s):
    rng = np.random.RandomState(0)
    b, h, d = 2, 3, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_padding_path_causal():
    """Non-tile-multiple sequence lengths are padded; padded keys are
    causally invisible so results still match."""
    rng = np.random.RandomState(1)
    b, s, h, d = 1, 200, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    ref = attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_flash_gradients():
    rng = np.random.RandomState(2)
    b, s, h, d = 1, 128, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    g1 = jax.grad(
        lambda q_: flash_attention(q_, k, v, causal=True,
                                   interpret=True).sum()
    )(q)
    g2 = jax.grad(
        lambda q_: attention_reference(q_, k, v, causal=True).sum()
    )(q)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), atol=5e-5, rtol=5e-5
    )


def test_flash_bf16_finite():
    q = jnp.ones((1, 128, 2, 32), jnp.bfloat16)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_dispatch_falls_back_on_cpu():
    """Without interpret, CPU dispatch uses the reference path (no
    pallas TPU lowering attempted)."""
    q = jnp.ones((1, 16, 1, 8), jnp.float32)
    out = flash_attention(q, q, q, causal=True)
    assert out.shape == q.shape


@pytest.mark.parametrize("bq,bkv", [(64, 128), (128, 64), (256, 128)])
def test_tunable_tiles_match_reference(bq, bkv):
    """ISSUE 19: block_q/block_kv are autotuner search axes — every
    tile pair (including asymmetric ones, which force lcm padding of
    a non-multiple sequence) must be an equivalence-preserving
    reparameterization of the SAME attention."""
    rng = np.random.RandomState(7)
    b, s, h, d = 1, 200, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=bq,
                          block_kv=bkv, interpret=True)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_tunable_tiles_gradients_match():
    """The tile pair rides the custom_vjp nondiff args — the backward
    kernel must honor the same tiles the forward ran with."""
    rng = np.random.RandomState(8)
    b, s, h, d = 1, 192, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    g1 = jax.grad(
        lambda q_: flash_attention(q_, k, v, causal=True, block_q=64,
                                   block_kv=128, interpret=True).sum()
    )(q)
    g2 = jax.grad(
        lambda q_: attention_reference(q_, k, v, causal=True).sum()
    )(q)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), atol=5e-5, rtol=5e-5
    )


@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_all_grads_match(causal):
    """The fused pallas backward must match dense-attention autodiff for
    dq, dk, AND dv (the old custom_vjp recomputed densely)."""
    rng = np.random.RandomState(7)
    b, s, h, d = 2, 256, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    cot = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    def loss_flash(q_, k_, v_):
        return (flash_attention(q_, k_, v_, causal=causal,
                                interpret=True) * cot).sum()

    def loss_ref(q_, k_, v_):
        return (attention_reference(q_, k_, v_, causal=causal) * cot).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g_flash, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch",
        )


def test_fused_backward_padded_seq():
    """Backward through the padding path (non-tile seq, causal)."""
    rng = np.random.RandomState(8)
    b, s, h, d = 1, 200, 2, 16
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    g1 = jax.grad(lambda q_: flash_attention(
        q_, q, q, causal=True, interpret=True).sum())(q)
    g2 = jax.grad(lambda q_: attention_reference(
        q_, q, q, causal=True).sum())(q)
    np.testing.assert_allclose(
        np.asarray(g1), np.asarray(g2), atol=1e-4, rtol=1e-4
    )


# -- ISSUE 25: tiles from the shape, masks on the diagonal only -------------

KERNELS = ("fwd", "dq", "dkv")


MIB = 2 ** 20


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("s", [64, 128, 200, 2048, 8192, 32768, 65536])
def test_tile_rule(s, d, kernel):
    """The rule's tiles, for the sequence as ``flash_attention`` pads
    it: they divide it, a block's last two dimensions are multiples of
    (8, 128) or the whole dimension, and the VMEM the rule reckons
    stays under its ceiling, at the cells' S and far past it. A kernel
    asks Mosaic for more than its default exactly where the reckoning
    is past what the default was trusted with, and then for at least
    the reckoning."""
    from sparkdl_tpu.ops._dispatch import block_for

    padded = -(-s // block_for(s)) * block_for(s)
    t = flash_kernels.flash_tiles(kernel, padded, d, 2)
    assert padded % t.bq == 0 and padded % t.bk == 0
    assert padded % t.major == 0
    assert t.major % t.bq == 0 and t.major % t.bk == 0
    assert max(t.bq, t.bk) % min(t.bq, t.bk) == 0      # the tiles nest
    for rows in (t.bq, t.bk, t.major):      # second-to-last block dim
        assert rows % 8 == 0 or rows == padded
    # dk/dv's lse and delta rows are (1, major) blocks: major is lanes
    assert t.major % 128 == 0 or t.major == padded
    # a v5e core has 128 MiB of VMEM and Mosaic scopes 16 of it unasked
    assert t.vmem_bytes <= flash_kernels.VMEM_BUDGET < 128 * MIB // 2
    assert (t.vmem_limit > 0) == (t.vmem_bytes > 12 * MIB)
    assert t.vmem_limit == 0 or (
        t.vmem_bytes + 2 * MIB <= t.vmem_limit <= 128 * MIB // 2)
    assert t == flash_kernels.flash_tiles(kernel, padded, d, 2)   # pure
    if padded >= 512:
        # several MXU passes deep, not the 128 of before
        assert min(t.bq, t.bk) >= 256
    # the streamed side is ONE major block at the cells' S = 8192 and
    # through 32768 (no scratch, the state in the walk's carry); past
    # the ceiling it comes in major blocks and VMEM stops growing with S
    assert (t.major == padded) == (s <= 32768)
    if s == 2048:
        # the tiles PR 25 tuned there, and nothing asked of Mosaic
        assert t[:3] == (512, 512, 2048) and t.vmem_limit == 0


def test_tile_rule_keeps_explicit_tiles():
    """An explicit tile wins over the rule, for every kernel."""
    for kernel in KERNELS:
        t = flash_kernels.flash_tiles(kernel, 2048, 128, 2, bq=128, bk=256)
        assert (t.bq, t.bk) == (128, 256)
        t = flash_kernels.flash_tiles(kernel, 2048, 128, 2, bk=128)
        assert t.bk == 128 and t.bq >= 256
    with pytest.raises(ValueError, match="divisible"):
        flash_kernels.flash_tiles("fwd", 2048, 128, 2, bq=384)
    with pytest.raises(ValueError, match="nest"):
        flash_kernels.flash_tiles("fwd", 1536, 128, 2, bq=768, bk=512)


def _qkv(rng, b, s, h, d, dtype):
    return [jnp.asarray(rng.randn(b, s, h, d), dtype) for _ in range(4)]


def _all_grads(fn, q, k, v, cot):
    return jax.grad(
        lambda *a: (fn(*a).astype(jnp.float32) * cot.astype(jnp.float32)
                    ).sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.fixture
def tiles_256(monkeypatch):
    """The rule with its targets cut to 256 (the CPU interprets the
    kernels): at S = 1024 a q tile then walks up to three tiles wholly
    below the diagonal and one across it, as a 512 tile does at the
    cell's 2048."""
    monkeypatch.setattr(flash_kernels, "_TARGET_TILE", 256)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s", [1024, 900], ids=["aligned", "padded"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "bidirectional"])
def test_rule_tiles_interior_and_diagonal(tiles_256, causal, s, dtype):
    """Forward and all three gradients against the reference where
    plain and masked tiles both occur, with tiles the rule chose."""
    rng = np.random.RandomState(11)
    q, k, v, cot = _qkv(rng, 1, s, 2, 32, dtype)
    assert flash_kernels.flash_tiles("fwd", 1024, 32, 4)[:2] == (256, 256)

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=causal, interpret=True)

    def ref(q_, k_, v_):
        return attention_reference(q_, k_, v_, causal=causal)

    f32 = dtype == jnp.float32
    tol = dict(atol=2e-5, rtol=2e-5) if f32 else dict(atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(ref(q, k, v), np.float32), **tol)
    gtol = dict(atol=1e-4, rtol=1e-4) if f32 else dict(atol=6e-2, rtol=6e-2)
    for name, got, want in zip(
            "qkv", _all_grads(flash, q, k, v, cot),
            _all_grads(ref, q, k, v, cot)):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=f"d{name}", **gtol)


@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (64, 256)])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "bidirectional"])
def test_major_blocks_carry_the_state(monkeypatch, causal, bq, bk):
    """With VMEM for one tile a block only, the streamed side comes in
    several major blocks: the running softmax state and the gradient
    accumulators cross grid steps in scratch, and blocks past the
    diagonal are neither fetched nor walked. Same answers."""
    monkeypatch.setattr(flash_kernels, "VMEM_BUDGET", 1)
    t = flash_kernels.flash_tiles("fwd", 512, 32, 4, bq, bk)
    assert t.major == max(bq, bk) < 512 and t.vmem_bytes > 1
    rng = np.random.RandomState(12)
    q, k, v, cot = _qkv(rng, 1, 512, 2, 32, jnp.float32)

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=causal, block_q=bq,
                               block_kv=bk, interpret=True)

    def ref(q_, k_, v_):
        return attention_reference(q_, k_, v_, causal=causal)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)),
        atol=2e-5, rtol=2e-5)
    for name, got, want in zip(
            "qkv", _all_grads(flash, q, k, v, cot),
            _all_grads(ref, q, k, v, cot)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name}")


@pytest.mark.parametrize("bq,bk", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window"])
def test_one_block_and_major_blocks_agree(monkeypatch, telemetry, window,
                                          bq, bk):
    """The kernels built with the streamed side whole (one grid step a
    tile, the state in the walk's carry) against the same kernels
    forced into one tile a major block (the state through scratch):
    forward, ``lse``, dq, dk and dv, causal and with a window that is
    no multiple of a tile. The entry points are called un-jitted, so
    that no cached trace stands in for either build, and ``flash.tiles``
    says that both were built."""
    rng = np.random.RandomState(14)
    q, k, v, do = (jnp.asarray(rng.randn(1, 2, 512, 32), jnp.float32)
                   for _ in range(4))

    def build():
        o, lse = flash_kernels.flash_attention_bhsd.__wrapped__(
            q, k, v, bq=bq, bk=bk, window=window, return_lse=True,
            interpret=True)
        delta = jnp.sum(do * o, -1, keepdims=True)
        return (o, lse) + flash_kernels.flash_attention_bwd_bhsd.__wrapped__(
            q, k, v, do, lse, delta, bq=bq, bk=bk, window=window,
            interpret=True)

    whole = build()
    monkeypatch.setattr(flash_kernels, "VMEM_BUDGET", 1)
    blocks = build()
    majors = sorted(
        (c["labels"]["kernel"], int(c["labels"]["major"]))
        for c in telemetry.metrics().snapshot()["counters"]
        if c["name"] == "flash.tiles")
    step = max(bq, bk)
    assert majors == [("dkv", step), ("dkv", 512), ("dq", step), ("dq", 512),
                      ("fwd", step), ("fwd", 512)]
    for name, got, want, tol in zip(
            ("o", "lse", "dq", "dk", "dv"), whole, blocks,
            (2e-5, 2e-5, 1e-4, 1e-4, 1e-4)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=tol, rtol=tol,
            err_msg=name)


def test_scale_is_folded_not_dropped():
    """A scale that is no power of two, through forward and backward:
    folded into q (forward, dq), k (dk/dv) and the final dq / dk."""
    rng = np.random.RandomState(13)
    q, k, v, cot = _qkv(rng, 1, 256, 2, 32, jnp.float32)

    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, scale=0.3,
                               block=128, interpret=True)

    def ref(q_, k_, v_):
        return attention_reference(q_, k_, v_, causal=True, scale=0.3)

    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)),
        atol=2e-5, rtol=2e-5)
    for got, want in zip(_all_grads(flash, q, k, v, cot),
                         _all_grads(ref, q, k, v, cot)):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_causal_keep_is_one_definition():
    """The transposed mask of dk/dv is the forward's, transposed."""
    keep = flash_kernels._causal_keep(64, 32, 16, 48)
    keep_t = flash_kernels._causal_keep(64, 32, 16, 48, transposed=True)
    assert keep.shape == (16, 48) and keep_t.shape == (48, 16)
    np.testing.assert_array_equal(np.asarray(keep).T, np.asarray(keep_t))
    rows, cols = np.nonzero(~np.asarray(keep))
    assert (64 + rows < 32 + cols).all() and len(rows)


def _tile_counts(observe):
    return {
        (c["labels"]["kernel"], c["labels"]["chosen"]):
            (c["value"], c["labels"])
        for c in observe.metrics().snapshot()["counters"]
        if c["name"] == "flash.tiles"}


def test_flash_tiles_counted_once_a_traced_kernel(telemetry):
    """``flash.tiles`` says which tiles a step was built with and who
    chose them: once a kernel when the call is traced, not once a run
    of the compiled program."""
    q = jnp.ones((1, 256, 1, 16), jnp.float32)
    step = jax.jit(jax.grad(lambda q_: flash_attention(
        q_, q_, q_, causal=True, interpret=True).sum()))
    step(q)
    step(q)                                  # cached: traces nothing
    counts = _tile_counts(telemetry)
    assert sorted(counts) == [
        ("dkv", "rule"), ("dq", "rule"), ("fwd", "rule")]
    for value, labels in counts.values():
        assert value == 1
        assert (labels["s"], labels["d"]) == ("256", "16")
        assert (labels["bq"], labels["bk"]) == ("256", "256")
        # the streamed side whole, inside what Mosaic scopes unasked
        assert (labels["major"], labels["vmem_limit"]) == ("256", "0")
    jax.jit(lambda q_: flash_attention(
        q_, q_, q_, causal=True, block_q=64, interpret=True))(q)
    value, labels = _tile_counts(telemetry)[("fwd", "argument")]
    assert value == 1 and (labels["bq"], labels["bk"]) == ("64", "256")


def test_flash_tiles_counter_says_what_was_asked_of_mosaic(telemetry):
    """At S = 8192 the forward's streamed side is whole and the kernel
    asks for the VMEM that takes (tracing counts; nothing runs)."""
    q = jax.ShapeDtypeStruct((1, 1, 8192, 128), jnp.bfloat16)
    jax.eval_shape(lambda q_: flash_kernels.flash_attention_bhsd(
        q_, q_, q_, interpret=True), q)
    (value, labels), = _tile_counts(telemetry).values()
    tiles = flash_kernels.flash_tiles("fwd", 8192, 128, 2)
    assert value == 1 and tiles.vmem_limit > 16 * MIB
    assert (labels["major"], labels["vmem_limit"]) == (
        "8192", str(tiles.vmem_limit))


def test_flash_tiles_not_counted_with_telemetry_off():
    from sparkdl_tpu import observe

    observe._reset_for_tests()
    q = jnp.ones((1, 128, 1, 16), jnp.float32)
    flash_attention(q, q, q, causal=True, interpret=True)
    assert not _tile_counts(observe)


def test_env_tiles_still_override_the_rule(monkeypatch):
    """``SPARKDL_TPU_FLASH_BLOCK*`` are read once at import; set, they
    reach all three kernels as explicit tiles."""
    import importlib

    from sparkdl_tpu.ops import attention

    monkeypatch.setenv("SPARKDL_TPU_FLASH_BLOCK", "128")
    monkeypatch.setenv("SPARKDL_TPU_FLASH_BLOCK_KV", "64")
    try:
        importlib.reload(attention)
        assert attention._DEFAULT_FLASH_BLOCK_Q == 128
        assert attention._DEFAULT_FLASH_BLOCK_KV == 64
        seen = []
        monkeypatch.setattr(
            attention, "_flash_core",
            lambda q, k, v, causal, scale, bq, bk, interpret, window:
                seen.append((bq, bk)) or q)
        q = jnp.ones((1, 256, 1, 16), jnp.float32)
        attention.flash_attention(q, q, q, interpret=True)
        attention.flash_attention(q, q, q, block_q=256, interpret=True)
        assert seen == [(128, 64), (256, 64)]
    finally:
        monkeypatch.undo()
        importlib.reload(attention)
    assert attention._DEFAULT_FLASH_BLOCK_Q == 0


# -- ISSUE 35: a causal window, walked and not only masked -------------------


def _windowed_pair(window, **tiles):
    def flash(q_, k_, v_):
        return flash_attention(q_, k_, v_, causal=True, window=window,
                               interpret=True, **tiles)

    def ref(q_, k_, v_):
        return attention_reference(q_, k_, v_, causal=True, window=window)

    return flash, ref


def _assert_same(flash, ref, q, k, v, cot, tol=2e-5, gtol=1e-4):
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v), np.float32),
        np.asarray(ref(q, k, v), np.float32), atol=tol, rtol=tol)
    for name, got, want in zip(
            "qkv", _all_grads(flash, q, k, v, cot),
            _all_grads(ref, q, k, v, cot)):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=gtol, rtol=gtol, err_msg=f"d{name}")


def test_reference_window_is_the_written_mask():
    """``0 <= i - j < window``, written out in numpy."""
    rng = np.random.RandomState(20)
    q, k, v, _ = _qkv(rng, 1, 24, 2, 8, jnp.float32)
    got = np.asarray(attention_reference(q, k, v, window=5))
    i, j = np.arange(24)[:, None], np.arange(24)[None, :]
    seen = (i - j >= 0) & (i - j < 5)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) / 8 ** .5
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True),
                     np.asarray(v))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="causal"):
        attention_reference(q, k, v, causal=False, window=5)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=5, interpret=True)


@pytest.mark.parametrize("window", [1, 100, 256, 300, 512, 1000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_window_rule_tiles(tiles_256, window, dtype):
    """Forward, dq, dk and dv against the reference at S = 1024 with
    tiles of 256: windows below a tile, of a tile, not a multiple of
    one, of two, and just under the sequence: plain tiles, tiles that
    cross either edge, and tiles that cross both."""
    rng = np.random.RandomState(21)
    q, k, v, cot = _qkv(rng, 1, 1024, 2, 32, dtype)
    tol = (2e-5, 1e-4) if dtype == jnp.float32 else (2e-2, 6e-2)
    _assert_same(*_windowed_pair(window), q, k, v, cot, *tol)


@pytest.mark.parametrize("window", [1024, 5000])
def test_window_that_covers_the_sequence_is_the_causal_program(window):
    """At and past the sequence the window compiles to the causal
    kernels: the same jaxpr, the same bits."""
    rng = np.random.RandomState(22)
    q, k, v, cot = _qkv(rng, 1, 1024, 1, 32, jnp.float32)
    causal = lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True, interpret=True)
    flash, ref = _windowed_pair(window)
    for got, want in zip(
            (flash(q, k, v), *_all_grads(flash, q, k, v, cot)),
            (causal(q, k, v), *_all_grads(causal, q, k, v, cot))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    grads = lambda f: str(jax.make_jaxpr(
        lambda *a: _all_grads(f, *a, cot))(q, k, v))
    assert grads(flash) == grads(causal)
    assert grads(_windowed_pair(512)[0]) != grads(causal)


@pytest.mark.parametrize("window", [40, 128, 200, 384])
@pytest.mark.parametrize("bq,bk", [(128, 256), (256, 128), (64, 256),
                                   (128, 128)])
def test_window_across_major_blocks(monkeypatch, window, bq, bk):
    """One tile a major block: the window's edge and the diagonal fall
    in different grid steps, blocks before the edge are neither
    fetched nor walked, and the state crosses in scratch."""
    monkeypatch.setattr(flash_kernels, "VMEM_BUDGET", 1)
    assert flash_kernels.flash_tiles("dkv", 512, 32, 4, bq, bk).major < 512
    rng = np.random.RandomState(23)
    q, k, v, cot = _qkv(rng, 1, 512, 2, 32, jnp.float32)
    _assert_same(*_windowed_pair(window, block_q=bq, block_kv=bk),
                 q, k, v, cot)


def test_window_padded_sequence():
    rng = np.random.RandomState(24)
    q, k, v, cot = _qkv(rng, 2, 200, 2, 16, jnp.float32)
    _assert_same(*_windowed_pair(70), q, k, v, cot)


@pytest.mark.parametrize("upto", [True, False], ids=["keys", "queries"])
def test_streamed_blocks_hold_at_both_ends_of_a_window(upto):
    """The index maps fetch no block outside [first visible, last
    visible] of a stationary tile: checked against the written mask."""
    s, tile, major, window = 4096, 256, 512, 700
    block = flash_kernels._streamed_block(
        True, tile, major, upto=upto, window=window)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (i - j >= 0) & (i - j < window)
    if not upto:
        seen = seen.T            # rows: keys; columns: the queries
    for t in range(s // tile):
        cols = np.nonzero(seen[t * tile:(t + 1) * tile].any(0))[0]
        first, last = cols.min() // major, cols.max() // major
        held = [int(block(t, b)) for b in range(s // major)]
        assert held == [min(max(b, first), last)
                        for b in range(s // major)], (t, held)


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("s,tile,window,walked,causal", [
    (8192, 512, 2048, 70, 136), (8192, 1024, 2048, 21, 36),
    (8192, 512, None, 136, 136), (1024, 256, 1, 4, 10),
    (1024, 256, 300, 9, 10)])
def test_tiles_walked_counts_the_tiles_with_a_visible_pair(
        kernel, s, tile, window, walked, causal):
    t = flash_kernels.FlashTiles(tile, tile, s, 0)
    assert flash_kernels.tiles_walked(kernel, s, t, window) == walked
    assert flash_kernels.tiles_walked(kernel, s, t) == causal
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = (i >= j) & ((i - j < window) if window else True)
    assert seen.reshape(s // tile, tile, s // tile, tile).any(
        (1, 3)).sum() == walked


def test_flash_tiles_counter_says_the_window(telemetry):
    q = jnp.ones((1, 1024, 1, 16), jnp.float32)
    jax.jit(jax.grad(lambda q_: flash_attention(
        q_, q_, q_, causal=True, window=256, block=256,
        interpret=True).sum()))(q)
    counts = _tile_counts(telemetry)
    assert sorted(counts) == [
        ("dkv", "argument"), ("dq", "argument"), ("fwd", "argument")]
    for _, labels in counts.values():
        assert (labels["window"], labels["tiles_walked"],
                labels["tiles_causal"]) == ("256", "7", "10")
        assert (labels["major"], labels["vmem_limit"]) == ("1024", "0")
