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

On a TPU the scan runs as the Pallas kernels of
:mod:`sparkdl_tpu.ops.pallas.ssd_scan`, forward and backward (a chunk's
decay stays in VMEM and the state is carried across the chunks on the
chip), for the shapes :func:`~sparkdl_tpu.ops.pallas.ssd_scan.
ssd_blocks` takes; elsewhere, and for the other shapes, as the plain
``jax.numpy`` of :func:`_ssd_plain`, differentiated by JAX (the dispatch
of :mod:`sparkdl_tpu.ops.attention`). Which one a traced call took is
``observe``'s ``ssd.scan`` counter; the scan's share of the chip's
roofline is on the record (``ssd_scan_roofline.train_hybrid``).
"""

import functools

import jax
import jax.numpy as jnp

from sparkdl_tpu import observe
from sparkdl_tpu.ops._dispatch import pad_to, use_pallas as _use_pallas


def ssd_chunked(x, dt, A, B, C, D, chunk=128, state_dtype=jnp.float32,
                interpret=None):
    """``y`` (batch, seq, heads, head_dim) of the recurrence above.

    :param x: (batch, seq, heads, head_dim).
    :param dt: (batch, seq, heads), positive (after the softplus).
    :param A: (heads,), negative.
    :param B, C: (batch, seq, groups, state); ``heads // groups``
        consecutive heads share a group's B and C.
    :param D: (heads,), the skip.
    :param chunk: steps a chunk; a sequence that is no multiple of it
        is padded with steps that leave the state as it is (dt = 0).
    :param state_dtype: of the carried state; the kernels carry float32
        and leave any other to the plain path.
    :param interpret: None: the kernels on a TPU where they take the
        shape, the plain path elsewhere; False: the kernels compiled,
        whatever the backend; True: interpreted, whatever the tiling
        (tests).
    """
    from sparkdl_tpu.ops.pallas.ssd_scan import ssd_blocks

    b, s, h, p = x.shape
    g, n = B.shape[2:]
    blocks = None
    if state_dtype == jnp.float32 and (interpret is not None or _use_pallas()):
        blocks = ssd_blocks(h, p, g, n, chunk, x.dtype.itemsize,
                            tiled=not interpret)
    observe.inc("ssd.scan", path="pallas" if blocks else "jnp", seq=s,
                heads=h, head_dim=p, groups=g, state=n, chunk=chunk,
                heads_a_block=blocks.heads if blocks else 0)
    if blocks is None:
        return _ssd_plain(x, dt, A, B, C, D, chunk, state_dtype)
    # a chunk's rows and a block's columns, as the mixer has them; only
    # dt (heads numbers a step) changes layout: a head's steps as a row
    x2, B2, C2 = (pad_to(a.reshape(b, s, -1), chunk, 1)[0] for a in (x, B, C))
    dt_t = pad_to(dt.astype(jnp.float32).swapaxes(1, 2), chunk, 2)[0]
    y = _ssd_kernels(
        x2, dt_t, A.astype(jnp.float32)[:, None], B2, C2,
        jnp.repeat(D.astype(jnp.float32), p)[None],      # the skip a column
        (("heads", h), ("groups", g), ("chunk", chunk), ("blocks", blocks),
         ("interpret", bool(interpret))))
    return y[:, :s].reshape(b, s, h, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _ssd_kernels(x, dt_t, a, B, C, d_cols, static):
    """The kernels on what they take (:func:`~sparkdl_tpu.ops.pallas.
    ssd_scan.ssd_scan_fwd`'s arguments); `static` is its keywords."""
    from sparkdl_tpu.ops.pallas.ssd_scan import ssd_scan_fwd

    return ssd_scan_fwd(x, dt_t, a, B, C, d_cols, **dict(static))


def _ssd_kernels_fwd(x, dt_t, a, B, C, d_cols, static):
    from sparkdl_tpu.ops.pallas.ssd_scan import ssd_scan_fwd

    y, states = ssd_scan_fwd(x, dt_t, a, B, C, d_cols, save_states=True,
                             **dict(static))
    return y, (x, dt_t, a, B, C, d_cols, states)


def _ssd_kernels_bwd(static, res, dy):
    """The kernel's five cotangents, and the two sums over everything
    that a frozen ``A`` and ``D`` leave unused (XLA then drops them)."""
    from sparkdl_tpu.ops.pallas.ssd_scan import ssd_scan_bwd

    x, dt_t, a, B, C, d_cols, states = res
    dx, ddt_t, da_t, dB, dC = ssd_scan_bwd(
        x, dt_t, a, B, C, d_cols, dy, states, **dict(static))
    # a group's blocks of heads added up (one a group: nothing to add)
    b, s, groups = B.shape[0], B.shape[1], dict(static)["groups"]
    dB, dC = (
        t.reshape(b, s, groups, -1, B.shape[2] // groups).astype(jnp.float32)
        .sum(3).reshape(B.shape).astype(B.dtype) for t in (dB, dC))
    da = jnp.sum(da_t * dt_t, axis=(0, 2))[:, None]
    dd_cols = jnp.einsum("bsc,bsc->c", dy, x,
                         preferred_element_type=jnp.float32)[None]
    return dx, ddt_t, da, dB, dC, dd_cols


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def _ssd_plain(x, dt, A, B, C, D, chunk, state_dtype):
    """The scan in plain ``jax.numpy``, differentiated by JAX: the path
    off the TPU and for shapes the kernels do not take."""
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
