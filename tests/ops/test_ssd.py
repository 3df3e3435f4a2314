"""The chunked state-space scan against the recurrence it stands for."""

import jax
import jax.numpy as jnp
import pytest

from sparkdl_tpu.ops.ssd import ssd_chunked

CHUNK = 16
TOL = 2e-5      # float32 products, in another order


def recurrence(x, dt, A, B, C, D):
    """``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t +
    D x_t``, a step at a time."""
    b, s, h, p = x.shape
    g = B.shape[2]
    B, C = (jnp.repeat(a, h // g, axis=2) for a in (B, C))

    def step(state, at):
        x_t, dt_t, B_t, C_t = at
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :])
        return state, (state * C_t[:, :, None, :]).sum(-1) + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, B.shape[-1])), tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def inputs(seq, seed=0):
    b, h, p, g, n = 2, 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, seq, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, seq, h)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (b, seq, g, n)),
            jax.random.normal(k[4], (b, seq, g, n)), jnp.ones((h,)))


def chunked(*args, **kw):
    return ssd_chunked(*args, chunk=CHUNK, **kw)


def worst(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# a multiple of the chunk, one that is not, and one shorter than a chunk
@pytest.mark.parametrize("seq", [64, 45, 7])
def test_chunked_scan_is_the_recurrence_forward_and_backward(seq):
    args = inputs(seq)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        got = jax.jit(chunked)(*args)
        assert got.shape == want.shape and worst(got, want) < TOL
        every = tuple(range(6))
        want_g = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            recurrence(*a))), argnums=every))(*args)
        got_g = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            chunked(*a))), argnums=every))(*args)
    for name, g, w in zip("x dt A B C D".split(), got_g, want_g):
        assert worst(g, w) < TOL, name


def test_a_bfloat16_state_fails_the_same_tolerance():
    """The control: the tolerance is tight enough to see the carried
    state lose its low bits."""
    args = inputs(64)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        bad = jax.jit(lambda *a: chunked(*a, state_dtype=jnp.bfloat16))(*args)
    assert worst(bad, want) > 10 * TOL


def test_activations_in_bfloat16_keep_their_dtype_and_stay_close():
    args = inputs(64)
    low = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                for i, a in enumerate(args))
    got = jax.jit(chunked)(*low)
    assert got.dtype == jnp.bfloat16
    assert worst(got.astype(jnp.float32), jax.jit(recurrence)(*args)) < 0.05
