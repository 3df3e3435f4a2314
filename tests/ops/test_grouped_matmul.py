"""The grouped product: the kernel (interpreted) against the plain
lowering, forward and in both gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.ops import grouped_matmul as gm


def test_tiles_divide_the_published_widths():
    assert (gm._tile(1024), gm._tile(2688), gm._tile(4096)) == (1024, 896, 1024)
    assert (gm._tile(48), gm._tile(1100)) == (48, 1024)     # one ragged tile


@pytest.mark.parametrize("rows", [512, 300])    # whole tiles, and padded
def test_kernel_is_the_plain_lowering_forward_and_backward(rows):
    """Uneven groups, an empty one, and rows past the groups' end,
    which are no result in either."""
    k, n = 48, 160
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
