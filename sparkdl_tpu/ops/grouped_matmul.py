"""One matrix product a group of rows: the experts' projections.

``lhs`` holds the rows of every group one after another, group 0's
first; ``rhs`` holds one matrix a group. Work goes with the rows that
the groups hold, not with the groups times the rows.

On the TPU this is the Pallas kernel that JAX ships,
``jax.experimental.pallas.ops.tpu.megablox.gmm``, with tiles chosen from
the shape; elsewhere ``jax.lax.ragged_dot``'s plain lowering (the
dispatch of :mod:`sparkdl_tpu.ops.attention`). Both were timed on a v5e
at (180,224 rows of which 45,056 in groups, 128 groups, 1024 x 2688):
XLA's own TPU kernel for ``ragged_dot`` took 10.0 ms up and 7.0 ms down,
``gmm`` at these tiles 3.6 and 3.7 ms (a dense product of as many
operations: 2.0 ms; my chip run, PR 29, PERF.md), so ``gmm`` stayed.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from sparkdl_tpu.ops._dispatch import pad_to, use_pallas as _use_pallas

ROWS_TILE = 256     # of (128, 256, 512) the fastest for both projections
WIDEST_TILE = 1024
NAME = "sparkdl_moe_gmm"    # in the name stack of each kernel call


def _tile(dim):
    """The widest multiple of 128 that divides `dim`, up to 1024 (2688
    -> 896); a `dim` that is no multiple of 128 takes one ragged tile,
    which the kernel masks."""
    if dim % 128:
        return min(dim, WIDEST_TILE)
    return max(t for t in range(128, WIDEST_TILE + 1, 128) if dim % t == 0)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    k, n = rhs.shape[1:][::-1] if transpose_rhs else rhs.shape[1:]
    with jax.named_scope(NAME):
        return gmm(
            lhs, rhs, group_sizes, lhs.dtype, (ROWS_TILE, _tile(k), _tile(n)),
            transpose_rhs=transpose_rhs, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, False, interpret)


def _grouped_fwd(lhs, rhs, group_sizes, interpret):
    return _grouped(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _grouped_bwd(interpret, res, g):
    """The gradient in the rows through the transposed matrices, and in
    the matrices a group at a time; a frozen ``rhs`` leaves the second
    unused, and XLA drops it."""
    lhs, rhs, group_sizes = res
    k, n = rhs.shape[1:]
    with jax.named_scope(NAME):
        d_rhs = tgmm(
            lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
            (ROWS_TILE, _tile(k), _tile(n)), num_actual_groups=rhs.shape[0],
            interpret=interpret)
    return _gmm(g, rhs, group_sizes, True, interpret), d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, group_sizes, interpret=None):
    """``out[rows of group g] = lhs[rows of group g] @ rhs[g]``.

    :param lhs: (rows, k).
    :param rhs: (groups, k, n).
    :param group_sizes: (groups,) int32; the groups' rows lie first and
        in order. Rows past their sum are NOT computed: what the result
        holds there is no number to use, forward or backward.
    :param interpret: None: the kernel on a TPU, ``lax.ragged_dot``
        elsewhere; True: the kernel interpreted (tests).
    :returns: (rows, n) in ``lhs``'s dtype, accumulated in float32.
    """
    if interpret is None:
        if not _use_pallas():
            return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                      preferred_element_type=lhs.dtype)
        interpret = False
    rows = lhs.shape[0]
    lhs, _ = pad_to(lhs, ROWS_TILE, 0)
    return _grouped(lhs, rhs, group_sizes.astype(jnp.int32), interpret)[:rows]
