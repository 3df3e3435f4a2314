"""The chunked state-space scan against the recurrence it stands for:
the plain ``jax.numpy`` path and the Pallas kernels, interpreted."""

import functools

import jax
import jax.numpy as jnp
import pytest

from sparkdl_tpu.ops import ssd
from sparkdl_tpu.ops.pallas import ssd_scan
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


def inputs(seq, seed=0, b=2):
    """A batch of two, two groups of two heads."""
    h, p, g, n = 4, 8, 2, 16
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(k[0], (b, seq, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, seq, h)) - 2.0),
            -jnp.exp(jax.random.uniform(k[2], (h,), minval=0.0, maxval=2.7)),
            jax.random.normal(k[3], (b, seq, g, n)),
            jax.random.normal(k[4], (b, seq, g, n)), jnp.ones((h,)))


def chunked(*args, chunk=CHUNK, **kw):
    return ssd_chunked(*args, chunk=chunk, **kw)


def kernels(*args, chunk=CHUNK, **kw):
    """The Pallas kernels, interpreted (whatever the tiling)."""
    return ssd_chunked(*args, chunk=chunk, interpret=True, **kw)


PATHS = {"plain": chunked, "kernels": kernels}


def worst(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def is_the_recurrence(scan, args):
    """`scan` against the recurrence on `args`, forward and every
    gradient, finite and within ``TOL``."""
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        got = jax.jit(scan)(*args)
        every = tuple(range(6))
        want_g = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            recurrence(*a))), argnums=every))(*args)
        got_g = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(
            scan(*a))), argnums=every))(*args)
    assert got.shape == want.shape and worst(got, want) < TOL
    for name, g, w in zip("x dt A B C D".split(), got_g, want_g):
        assert g.shape == w.shape and worst(g, w) < TOL, name


# a multiple of the chunk (four chunks), two chunks, one that is no
# multiple, and one shorter than a chunk
@pytest.mark.parametrize("seq", [64, 32, 45, 7])
@pytest.mark.parametrize("path", PATHS)
def test_chunked_scan_is_the_recurrence_forward_and_backward(path, seq):
    is_the_recurrence(PATHS[path], inputs(seq))


def test_a_bfloat16_state_fails_the_same_tolerance():
    """The control: the tolerance is tight enough to see the carried
    state lose its low bits."""
    args = inputs(64)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        bad = jax.jit(lambda *a: chunked(*a, state_dtype=jnp.bfloat16))(*args)
    assert worst(bad, want) > 10 * TOL


@pytest.mark.parametrize("path", PATHS)
def test_activations_in_bfloat16_keep_their_dtype_and_stay_close(path):
    args = inputs(64)
    low = tuple(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                for i, a in enumerate(args))
    got = jax.jit(PATHS[path])(*low)
    assert got.dtype == jnp.bfloat16
    assert worst(got.astype(jnp.float32), jax.jit(recurrence)(*args)) < 0.05
    grads = jax.jit(jax.grad(
        lambda *a: PATHS[path](*a).astype(jnp.float32).sum(),
        argnums=(0, 1, 3, 4)))(*low)
    assert [g.dtype for g in grads] == [low[i].dtype for i in (0, 1, 3, 4)]


@pytest.mark.parametrize("path", PATHS)
def test_the_published_initialisation_over_a_whole_chunk_stays_finite(path):
    """``A = -16, dt = 0.1`` over the published chunk of 128: a chunk's
    log-decay reaches -204.8, and the control shows that the ratio of
    two exponentials is no way to a step's decay there. The scan takes
    the difference of the sums first."""
    chunk = 128
    x, dt, A, B, C, D = inputs(2 * chunk, b=1)
    dt, A = jnp.full_like(dt, 0.1), jnp.full_like(A, -16.0)
    cum = jnp.cumsum(dt[0, :chunk, 0] * A[0])
    assert not jnp.isfinite(jnp.exp(cum[1]) * jnp.exp(-cum[-1]))   # control
    is_the_recurrence(functools.partial(PATHS[path], chunk=chunk),
                      (x, dt, A, B, C, D))


def _scan_counts(observe):
    return [(c["value"], c["labels"])
            for c in observe.metrics().snapshot()["counters"]
            if c["name"] == "ssd.scan"]


def test_a_shape_the_kernels_do_not_take_goes_the_plain_way(
        telemetry, monkeypatch):
    """On a TPU (the test answers for one) a chunk of 16 lies on no
    (8, 128) tiling, and a bfloat16 state is not what the kernels
    carry: both run as the plain path, the same numbers as off the
    TPU, and ``ssd.scan`` says so, once a traced call."""
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    args = inputs(64)
    step = jax.jit(chunked)
    got = step(*args)
    step(*args)                              # cached: traces nothing
    monkeypatch.setattr(ssd, "_use_pallas", lambda: False)
    assert jnp.array_equal(got, jax.jit(lambda *a: chunked(*a))(*args))
    (value, labels), = _scan_counts(telemetry)
    assert value == 2                        # on the TPU, and off it
    assert labels == {
        "path": "jnp", "seq": "64", "heads": "4", "head_dim": "8",
        "groups": "2", "state": "16", "chunk": "16", "heads_a_block": "0"}
    monkeypatch.setattr(ssd, "_use_pallas", lambda: True)
    jax.jit(lambda *a: chunked(*a, state_dtype=jnp.bfloat16))(*args)
    assert [v for v, _ in _scan_counts(telemetry)] == [3]


def test_the_kernels_are_counted_with_their_blocks(telemetry):
    jax.jit(kernels)(*inputs(64))
    (value, labels), = _scan_counts(telemetry)
    assert value == 1 and labels["path"] == "pallas"
    assert (labels["seq"], labels["heads_a_block"]) == ("64", "2")


# (heads, head_dim, groups, state, chunk, itemsize): the cell's
# (Nemotron-3-Super), the same in float32, Mamba-2's own 2.7B, a head
# as wide as the lanes, and one group over all the heads
SHAPES = [(128, 64, 8, 128, 128, 2), (128, 64, 8, 128, 128, 4),
          (80, 64, 1, 128, 256, 2), (64, 128, 8, 128, 128, 2),
          (24, 64, 1, 128, 128, 2)]


@pytest.mark.parametrize("shape", SHAPES)
def test_block_rule(shape):
    """The rule's blocks: whole lane groups of one group's heads, every
    block's last two dimensions multiples of (8, 128) or the whole
    dimension, and the VMEM it reckons under the scoped limit."""
    heads, p, groups, n, chunk, itemsize = shape
    t = ssd_scan.ssd_blocks(*shape)
    assert t == ssd_scan.ssd_blocks(*shape)                      # pure
    assert (heads // groups) % t.heads == 0 and t.heads % t.lane_heads == 0
    assert (t.lane_heads * p) % 128 == 0          # a lane group of columns
    assert (t.heads * p) % 128 == 0               # x, y: a block's columns
    assert t.heads % 8 == 0 or t.heads == heads   # dt: (heads, chunk) rows
    assert chunk % 128 == 0 and n % 128 == 0      # B, C, the states
    # v5e scopes 16 MiB of VMEM a kernel
    assert t.vmem_bytes <= ssd_scan.VMEM_BUDGET < 16 * 2 ** 20
    if shape == SHAPES[0]:
        assert t.heads == 16                      # a whole group a program


@pytest.mark.parametrize("shape", [
    (4, 8, 2, 16, 16, 4),            # the tests' own: chunk and state of 16
    (128, 48, 8, 128, 128, 2),       # a head that shares no lane group
    (128, 64, 8, 64, 128, 2),        # half a lane group of state
    (12, 64, 3, 128, 128, 2)])       # 4 heads a group: no (8, 128) row block
def test_block_rule_refuses_what_the_tiling_cannot_hold(shape):
    assert ssd_scan.ssd_blocks(*shape) is None
    assert ssd_scan.ssd_blocks(*shape, tiled=False).heads == shape[0] // shape[2]
