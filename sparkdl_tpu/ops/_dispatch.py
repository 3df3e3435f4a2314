"""Shared kernel-dispatch helpers: backend probe, tile-padding."""

import jax
import jax.numpy as jnp


def use_pallas():
    """True when the default backend compiles pallas TPU kernels. A
    backend that fails to initialize raises here: answering "use XLA"
    instead would measure the wrong path on a sick TPU host."""
    return jax.default_backend() == "tpu"


def block_for(size, tile=128, floor=8):
    """Tile size for a dimension: the full tile when it fits, else a
    small multiple that at least satisfies sublane constraints."""
    return tile if size >= tile else max(floor, size)


def pad_to(x, multiple, axis):
    """Zero-pad ``axis`` up to a multiple; returns (padded, pad)."""
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad
