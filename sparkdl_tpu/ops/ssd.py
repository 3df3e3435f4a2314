"""The state-space scan of a Mamba-2 layer in its chunked form (SSD).

A head keeps a state ``h`` of (head_dim, state) and sees, a step,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T        y_t = h_t C_t + D x_t

Within a chunk of ``chunk`` steps the sum over earlier steps is a masked
product, ``(C B^T * decay) x``: matrix products, which is what the MXU
is for. Between chunks only the state is carried, one small step a
chunk. Products take their operands in the inputs' dtype and accumulate
in float32; the decays and the carried state stay in ``state_dtype``
(float32: a bfloat16 state loses the early tokens of a long sequence,
which ``tests/ops/test_ssd.py`` holds as its control).

Plain ``jax.numpy``, differentiated by JAX. Its share of the chip's
roofline is on the record (``ssd_scan_roofline.train_hybrid``).
"""

import jax
import jax.numpy as jnp


def ssd_chunked(x, dt, A, B, C, D, chunk=128, state_dtype=jnp.float32):
    """``y`` (batch, seq, heads, head_dim) of the recurrence above.

    :param x: (batch, seq, heads, head_dim).
    :param dt: (batch, seq, heads), positive (after the softplus).
    :param A: (heads,), negative.
    :param B, C: (batch, seq, groups, state); ``heads // groups``
        consecutive heads share a group's B and C.
    :param D: (heads,), the skip.
    :param chunk: steps a chunk; a sequence that is no multiple of it
        is padded with steps that leave the state as it is (dt = 0).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    c = (s + pad) // chunk
    f32 = jnp.float32
    # heads by group: (batch, chunks, steps, groups, heads a group, ...)
    xc = x.reshape(b, c, chunk, g, h // g, p)
    dtc = dt.astype(f32).reshape(b, c, chunk, g, h // g)
    Bc = B.reshape(b, c, chunk, g, n)
    Cc = C.reshape(b, c, chunk, g, n)

    # log-decay from a chunk's start up to and with each step
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(g, h // g), axis=2)
    total = cum[:, :, -1]                              # (b, c, g, r)

    # within a chunk: step i reads step j <= i through C_i.B_j, decayed
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=f32)
    cum_h = jnp.moveaxis(cum, 2, -1)                   # (b, c, g, r, l)
    seg = cum_h[..., :, None] - cum_h[..., None, :]    # (b, c, g, r, i, j)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    weights = (scores[:, :, :, None] * decay
               * jnp.moveaxis(dtc, 2, -1)[..., None, :])
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights.astype(x.dtype), xc,
                   preferred_element_type=f32)

    # what each chunk adds to the state by its end
    to_end = jnp.exp(total[:, :, None] - cum) * dtc    # (b, c, l, g, r)
    added = jnp.einsum("bclgrp,bclgn->bcgrpn",
                       xc * to_end[..., None].astype(x.dtype), Bc,
                       preferred_element_type=f32).astype(state_dtype)

    # between chunks: the state a chunk starts from
    def carry(state, chunk_):
        keep, add = chunk_
        new = state * keep[..., None, None].astype(state_dtype) + add
        return new, state

    keep = jnp.exp(total)
    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, g, h // g, p, n), state_dtype),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)            # (b, c, g, r, p, n)
    y = y + jnp.einsum(
        "bclgn,bcgrpn->bclgrp", Cc, entering.astype(x.dtype),
        preferred_element_type=f32) * jnp.exp(cum)[..., None]

    y = y.reshape(b, s + pad, h, p)[:, :s]
    skip = x[:, :s].astype(f32) * D.astype(f32)[:, None]
    return (y + skip).astype(x.dtype)
