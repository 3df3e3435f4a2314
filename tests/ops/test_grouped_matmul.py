"""The grouped product: the tiling rule as a function of the shape, and
the kernel (interpreted) against the plain lowering, forward and in both
gradients, with the contraction whole and split."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import grouped_matmul as gm

# (k, n) of each expert cell's products, forward and transposed: glm's
# gate|up and down, trinity's, the hybrid's relu2 up and down
CELL_PRODUCTS = [(2048, 3072), (3072, 2048), (1536, 2048), (2048, 1536),
                 (2048, 2048), (1024, 2048), (2048, 1024),
                 (1024, 2688), (2688, 1024)]


def test_tiles_divide_the_published_widths():
    """The columns' tile is the widest multiple of 128 that divides the
    width and fits (2688 -> 896); a width that is no multiple of 128
    takes one ragged tile, which the kernel masks; the contraction is
    whole at any width, 48 and 1100 among them. ``tgmm`` keeps the
    widest tile of each side (``_tile``)."""
    assert gm.gmm_tiles(1024, 2688, 2, 2)[:3] == (256, 1024, 896)
    assert gm.gmm_tiles(2688, 4096, 2, 2)[:3] == (256, 2688, 512)
    assert gm.gmm_tiles(48, 160, 4, 4)[:3] == (256, 48, 160)
    assert gm.gmm_tiles(1100, 1100, 2, 2)[:3] == (256, 1100, 1024)
    assert (gm._tile(1024), gm._tile(2688), gm._tile(4096)) == (1024, 896, 1024)
    assert (gm._tile(48), gm._tile(1100)) == (48, 1024)     # one ragged tile


@pytest.mark.parametrize("k,n", CELL_PRODUCTS)
def test_the_cells_products_take_the_whole_contraction(k, n):
    """At every width the cells run, forward and transposed: one ``k``
    tile, the widest ``n`` tile whose reckoning is under the budget, and
    the next wider one (where there is one) over it."""
    tiles = gm.gmm_tiles(k, n, 2, 2)
    assert (tiles.tm, tiles.tk) == (gm.ROWS_TILE, k)
    assert n % tiles.tn == 0 and tiles.tn % 128 == 0
    assert tiles.vmem_bytes == gm._vmem_bytes(*tiles[:3], 2, 2)
    assert tiles.vmem_bytes <= gm.VMEM_BUDGET
    wider = [t for t in gm._divisors(n, gm.WIDEST_TILE) if t > tiles.tn]
    if wider:
        assert gm._vmem_bytes(256, k, min(wider), 2, 2) > gm.VMEM_BUDGET
    assert gm.gmm_tiles(k, n, 2, 2) == tiles          # pure


def test_the_contraction_is_split_only_where_128_columns_do_not_fit():
    """Mixtral's down projection, k = 14336: a 256 x 14336 lhs block
    alone reckons 14 MiB, so ``k`` is split by the widest multiple of
    128 that divides it and fits beside 1024 columns; its up projection
    stays whole, 256 columns wide. Float32 halves what fits."""
    assert 2 * 256 * 14336 * 2 > gm.VMEM_BUDGET
    down = gm.gmm_tiles(14336, 4096, 2, 2)
    assert down[:3] == (256, 1792, 1024)
    assert down.vmem_bytes <= gm.VMEM_BUDGET
    assert gm._vmem_bytes(256, 1792 + 128, 1024, 2, 2) > gm.VMEM_BUDGET
    assert gm.gmm_tiles(4096, 14336, 2, 2)[:3] == (256, 4096, 256)
    assert gm.gmm_tiles(2048, 3072, 4, 4)[:3] == (256, 2048, 384)


def _counted(observe):
    return sorted(
        (c["labels"]["kernel"], c["value"], c["labels"])
        for c in observe.metrics().snapshot()["counters"]
        if c["name"] == "gmm.tiles")


@pytest.mark.parametrize("rows,k,n,contraction", [
    pytest.param(512, 48, 160, "whole", id="512"),     # whole tiles
    pytest.param(300, 48, 160, "whole", id="300"),     # and padded
    pytest.param(512, 256, 384, "whole", id="512-k256"),
    pytest.param(512, 256, 384, "split", id="512-k256-split"),
    pytest.param(300, 256, 384, "split", id="300-k256-split"),
])
def test_kernel_is_the_plain_lowering_forward_and_backward(
        rows, k, n, contraction, telemetry, monkeypatch):
    """Uneven groups, an empty one, and rows past the groups' end,
    which are no result in either; widths that are no multiple of 128;
    the contraction in one tile, and (a budget that not even one
    whole-``k`` tile fits) split."""
    if contraction == "split":
        monkeypatch.setattr(gm, "VMEM_BUDGET", 10 ** 6)
    sizes = jnp.array([7, 0, 130, 61, 1], jnp.int32)
    held = int(sizes.sum())
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(keys[0], (rows, k))
    rhs = jax.random.normal(keys[1], (5, k, n))
    weight = jax.random.normal(keys[2], (rows, n)).at[held:].set(0.0)

    def loss(product):
        return lambda l, r: jnp.sum(product(l, r)[:held] * weight[:held])

    kernel = lambda l, r: gm.grouped_matmul(l, r, sizes, interpret=True)
    plain = lambda l, r: gm.grouped_matmul(l, r, sizes)   # off the TPU
    with jax.default_matmul_precision("highest"):
        got, want = kernel(lhs, rhs), plain(lhs, rhs)
        got_g = jax.grad(loss(kernel), argnums=(0, 1))(lhs, rhs)
        want_g = jax.grad(loss(plain), argnums=(0, 1))(lhs, rhs)
    assert got.shape == want.shape == (rows, n)
    np.testing.assert_allclose(got[:held], want[:held], atol=1e-4)
    np.testing.assert_allclose(got_g[0][:held], want_g[0][:held], atol=1e-4)
    np.testing.assert_allclose(got_g[1], want_g[1], atol=1e-4)
    want_first = lhs[:7] @ rhs[0]
    np.testing.assert_allclose(got[:7], want_first, atol=1e-4)
    built = {(kernel, labels["tiles_k"]) for kernel, _, labels in
             _counted(telemetry)}
    # split, the forward's 256 in two tiles and the backward's 384 in three
    want_k = {"whole": ("1", "1"), "split": ("2", "3")}[contraction]
    assert built == {("gmm", want_k[0]), ("gmm_t", want_k[1])}


def test_the_tiles_are_counted_once_a_traced_call(telemetry):
    """``gmm.tiles`` at glm's gate|up product: the forward and the
    transposed backward once each a trace, the contraction in one tile,
    and nothing more when the traced step is called again."""
    sizes = jnp.array([256, 0, 200, 56], jnp.int32)
    lhs = jax.ShapeDtypeStruct((512, 2048), jnp.bfloat16)
    rhs = jax.ShapeDtypeStruct((4, 2048, 3072), jnp.bfloat16)
    step = jax.jit(jax.grad(
        lambda l, r: gm.grouped_matmul(l, r, sizes, interpret=True)
        .astype(jnp.float32).sum()))
    step.lower(lhs, rhs)
    step.lower(lhs, rhs)                  # cached: traces nothing
    (fwd, n_fwd, labels), (bwd, n_bwd, labels_t) = _counted(telemetry)
    assert (fwd, n_fwd, bwd, n_bwd) == ("gmm", 1, "gmm_t", 1)
    assert labels == {
        "kernel": "gmm", "k": "2048", "n": "3072", "tm": "256",
        "tk": "2048", "tn": "768", "tiles_k": "1",
        "vmem": str(gm.gmm_tiles(2048, 3072, 2, 2).vmem_bytes)}
    assert (labels_t["k"], labels_t["n"], labels_t["tk"], labels_t["tn"],
            labels_t["tiles_k"]) == ("3072", "2048", "3072", "512", "1")


def test_nothing_is_counted_with_telemetry_off():
    from sparkdl_tpu import observe

    observe._reset_for_tests()
    assert not observe.enabled()
    sizes = jnp.array([256, 256], jnp.int32)
    jax.jit(lambda l, r: gm.grouped_matmul(l, r, sizes, interpret=True)).lower(
        jax.ShapeDtypeStruct((512, 256), jnp.float32),
        jax.ShapeDtypeStruct((2, 256, 128), jnp.float32))
    assert not [c for c in observe.metrics().snapshot()["counters"]
                if c["name"] == "gmm.tiles"]
