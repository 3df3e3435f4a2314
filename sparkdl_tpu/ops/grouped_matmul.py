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

**The contraction is one tile.** ``gmm`` walks the grid (``n`` tiles,
rows tiles, ``k`` tiles), ``k`` innermost. Split over ``k``, the
matrix's block changes on every step, so each rows tile of an expert
fetches the expert's whole matrix again; in one ``k`` tile two rows
tiles of one expert in a row keep the block, and the pipeline fetches
it once. :func:`gmm_tiles` therefore takes the whole contraction and
the widest ``n`` tile whose reckoned VMEM fits the scope Mosaic gives a
kernel that asks for none (``gmm`` asks for none), and splits ``k`` only
where not even 128 columns fit. ``observe``'s ``gmm.tiles`` counter says
which tiles each traced call was built with.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from sparkdl_tpu import observe
from sparkdl_tpu.ops._dispatch import pad_to, use_pallas as _use_pallas

ROWS_TILE = 256     # of (128, 256, 512) the fastest for both projections
WIDEST_TILE = 1024
_LANES = 128
# Mosaic scopes 16 MiB of a v5e core's VMEM to a kernel that asks for no
# more, and ``gmm`` asks for none. `_vmem_bytes` reckons a little over
# what Mosaic takes (compiled for a v5e at 3072 x 1024 tiles: 18.0 MiB
# reckoned, 17.71 taken and refused); the budget leaves the scope's
# last quarter to spare.
VMEM_BUDGET = 12 * 2 ** 20
NAME = "sparkdl_moe_gmm"    # in the name stack of each kernel call


class GmmTiles(NamedTuple):
    """One product's tiling: ``tm`` rows x ``tk`` of the contraction x
    ``tn`` columns, ``vmem_bytes`` reckoned."""

    tm: int
    tk: int
    tn: int
    vmem_bytes: int


def _divisors(dim, widest):
    """The multiples of 128 that divide `dim`, up to `widest`, widest
    first."""
    return [t for t in range(widest - widest % _LANES, 0, -_LANES)
            if dim % t == 0]


def _tile(dim):
    """The widest multiple of 128 that divides `dim`, up to 1024 (2688
    -> 896); a `dim` that is no multiple of 128 takes one ragged tile,
    which the kernel masks."""
    return (_divisors(dim, WIDEST_TILE) or [min(dim, WIDEST_TILE)])[0]


def _vmem_bytes(tm, tk, tn, itemsize, out_itemsize):
    """VMEM one program of ``gmm`` needs: the lhs, rhs and out blocks
    double-buffered, the float32 accumulator and the float32 product."""
    return (2 * (tm * tk + tk * tn) * itemsize + 2 * tm * tn * out_itemsize
            + 2 * tm * tn * 4)


def gmm_tiles(k, n, itemsize, out_itemsize):
    """Tiles of a grouped product over a contraction of `k` into `n`
    columns, operands of `itemsize` bytes and a result of
    `out_itemsize`: a pure function of the shape.

    The rows tile is ``ROWS_TILE``; the contraction is whole (a block as
    wide as the array is legal at any width) and ``tn`` the widest of
    :func:`_tile`'s widths whose reckoning stays under ``VMEM_BUDGET``.
    Only where not even 128 columns fit beside the whole contraction is
    ``k`` split, by the widest multiple of 128 that divides it and fits,
    the columns again as wide as fit (split, the matrices are read once
    a rows tile whatever ``tk``, and the rows once an ``n`` tile).
    """
    widths = _divisors(n, WIDEST_TILE) or [_tile(n)]
    tries = [(k, tn) for tn in widths] + [
        (tk, tn) for tn in widths for tk in _divisors(k, k - 1)]
    for tk, tn in tries:
        need = _vmem_bytes(ROWS_TILE, tk, tn, itemsize, out_itemsize)
        if need <= VMEM_BUDGET:
            break
    return GmmTiles(ROWS_TILE, tk, tn, need)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret):
    k, n = rhs.shape[1:][::-1] if transpose_rhs else rhs.shape[1:]
    tiles = gmm_tiles(k, n, lhs.dtype.itemsize, lhs.dtype.itemsize)
    observe.inc("gmm.tiles", kernel="gmm_t" if transpose_rhs else "gmm",
                k=k, n=n, tm=tiles.tm, tk=tiles.tk, tn=tiles.tn,
                tiles_k=-(-k // tiles.tk), vmem=tiles.vmem_bytes)
    with jax.named_scope(NAME):
        return gmm(
            lhs, rhs, group_sizes, lhs.dtype, tiles[:3],
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
