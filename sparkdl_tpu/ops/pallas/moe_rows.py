"""Pallas TPU kernels that move rows between a layer's tokens and the
buffer of (token, pick) pairs sorted by expert: ``sparkdl_moe_take`` and
``sparkdl_moe_add`` (the names their events take in a device trace).
Each is the other's transpose, and both visit only the first ``n`` rows
of the sorted buffer, the pairs that landed on this chip
(:func:`sparkdl_tpu.models.moe.latent_experts`).

- **take**: ``rows[r] = src[tok[r]]`` for ``r < n``.
- **add**: ``out[t] = sum over r < n with tok[r] == t of w[r] * y[r]``,
  accumulated in float32.

What this file decides is where the numbers live.

- **The token side stays in VMEM, the row side streams.** A single row
  of a bfloat16 matrix cannot be addressed in HBM (two rows share each
  32-bit word of its tiling), so no DMA gathers one. Instead the
  (tokens, width) side, the source of a take or the sum of an add, is
  held whole in VMEM as float32 (32 MiB at 8192 x 1024), and the grid
  runs over tiles of ``ROWS_TILE`` rows of the sorted buffer, which the
  pipeline copies a tile at a time.
- **A token is one aligned slab.** The token side is laid out as
  (tokens x width / 128, 128): a token's row is ``width / 128``
  consecutive sublanes, whole (8, 128) tiles where the width is a
  multiple of 1024. A row then moves by one aligned load and one aligned
  store at an offset read from SMEM; the tile turns between slabs and
  (rows, width) by strided loads and stores, a lane group at a time.
- **Work follows n.** ``n`` is a prefetched scalar. A grid step whose
  tile starts at or past ``n`` does nothing, and its block index is
  clamped to the last live tile, so the pipeline issues no copy for it
  (as ``megablox.gmm`` does with its group metadata). A take writes
  nothing past the last live tile and an add reads nothing past ``n``:
  what the sorted buffer holds past ``n`` is no number to use.

On a v5e at the benchmark cell's shape (44,444 live of 180,224 rows of
1024 bfloat16, 8192 tokens) a take alone takes 0.28 ms, with weights and
dots 0.43, an add 0.58; XLA's gathers over the whole buffer, which the
plain path keeps, take 0.75 ms on the way out, 5.4 on the way back with
its weighted sum and 8.9 for that one's gradient (my chip run, PR 32,
PERF.md).
"""

import functools

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops._dispatch import pad_to

_LANES = 128
_F32 = jnp.float32
# an int32 vector's tile in SMEM: a block of the index vector is one
ROWS_TILE = 1024
# the token side held in VMEM: the benchmark cell's 8192 x 1024 float32
RESIDENT_BYTES = 32 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))   # a @ b^T


def takes_shape(tokens, width, tiled=True):
    """Whether the kernels take (tokens, width): a pure function of the
    shape. Compiled, a token's slab is whole (8, 128) float32 tiles and
    the token side fits ``RESIDENT_BYTES``; interpreted (tests) any
    whole lane groups."""
    if width % _LANES:
        return False
    return not tiled or (width % (8 * _LANES) == 0
                         and tokens * width * 4 <= RESIDENT_BYTES)


def _slab(index, sub, pl):
    """The `sub` sublanes of slab `index`."""
    return pl.ds(pl.multiple_of(index * sub, sub), sub)


def _make_take_kernel(tile, sub, weighted):
    from jax.experimental import pallas as pl

    def kernel(n_ref, tok_ref, *refs):
        if weighted:
            w_ref, src_ref, y_ref, out_ref, dw_ref, slabs, scaled = refs
        else:
            src_ref, out_ref, slabs = refs

        @pl.when(pl.program_id(0) * tile < n_ref[0])
        def _():
            def rows(j8, carry):
                for u in range(8):
                    j = j8 * 8 + u
                    row = src_ref[_slab(tok_ref[j], sub, pl), :]
                    slabs[_slab(j, sub, pl), :] = row
                    if weighted:
                        scaled[_slab(j, sub, pl), :] = w_ref[j] * row
                return carry

            jax.lax.fori_loop(0, tile // 8, rows, 0)
            dots = None
            for c in range(sub):
                lanes = slice(c * _LANES, (c + 1) * _LANES)
                group = pl.ds(c, tile, stride=sub)
                out_ref[:, lanes] = (scaled if weighted else slabs)[
                    group, :].astype(out_ref.dtype)
                if weighted:
                    dot = slabs[group, :] * y_ref[:, lanes].astype(_F32)
                    dots = dot if dots is None else dots + dot
            if weighted:
                # a row's sum over its lanes, as a row of lanes: a
                # product with ones, which the MXU does in any layout
                dw_ref[0] = jax.lax.dot_general(
                    jnp.ones((8, _LANES), _F32), dots, _NT,
                    preferred_element_type=_F32,
                    precision=jax.lax.Precision.HIGHEST)[:1]

    return kernel


def _make_add_kernel(tile, sub):
    from jax.experimental import pallas as pl

    def kernel(n_ref, tok_ref, w_ref, y_ref, out_ref, slabs):
        start = pl.program_id(0) * tile

        @pl.when(start == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(start < n_ref[0])
        def _():
            for c in range(sub):
                slabs[pl.ds(c, tile, stride=sub), :] = y_ref[
                    :, c * _LANES:(c + 1) * _LANES].astype(_F32)

            def row(j, carry):
                at = _slab(tok_ref[j], sub, pl)
                out_ref[at, :] = (out_ref[at, :]
                                  + w_ref[j] * slabs[_slab(j, sub, pl), :])
                return carry

            jax.lax.fori_loop(0, jnp.minimum(n_ref[0] - start, tile), row, 0)

    return kernel


def _specs(tile, width, tokens):
    """Block specifications for grid step i with `n` prefetched: a tile
    of a row vector in SMEM, a tile of the sorted buffer, and the token
    side whole and buffered once. A step's block index is i while its
    tile holds a row before `n`, then the last such tile's: no copy is
    issued for a block that does not change."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def live(i, n_ref):
        return jnp.minimum(i, jnp.maximum((n_ref[0] - 1) // tile, 0))

    vector = pl.BlockSpec((tile,), lambda i, n_ref: (live(i, n_ref),),
                          memory_space=pltpu.SMEM)
    block = pl.BlockSpec((tile, width), lambda i, n_ref: (live(i, n_ref), 0))
    dots = pl.BlockSpec((1, 1, tile), lambda i, n_ref: (live(i, n_ref), 0, 0))
    resident = pl.BlockSpec(
        (tokens * width // _LANES, _LANES), lambda i, n_ref: (0, 0),
        pipeline_mode=pl.Buffered(1))
    return vector, block, dots, resident


def _call(kernel, name, n, operands, in_specs, out_shape, out_specs, *,
          scratch, tokens, width, tile, interpret):
    """One kernel over the tiles of the sorted buffer (`operands[0]` is
    a row vector), with `scratch` buffers of a tile's slabs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(operands[0].shape[0] // tile,),
            in_specs=in_specs, out_specs=out_specs, scratch_shapes=[
                pltpu.VMEM((tile * width // _LANES, _LANES), _F32)
            ] * scratch),
        out_shape=out_shape,
        # the token side once, and the tile's blocks and slabs
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=tokens * width * 4 + 16 * tile * width + 2 ** 22),
        interpret=interpret,
        name=name,
    )(jnp.reshape(n, (1,)).astype(jnp.int32), *operands)


# Jitted INLINE, as the other kernels' entry points are: the program is
# the same, but a kernel body is traced once a shape and not once a layer
# and pass (a remat step of five expert layers calls these twenty-five
# times).
@functools.partial(jax.jit, inline=True, static_argnames=("tile", "interpret"))
def take_rows(src, tok, n, weights=None, partner=None, *, tile=ROWS_TILE,
              interpret=False):
    """``rows[r] = src[tok[r]]`` for ``r < n``, in ``src``'s dtype (in
    ``partner``'s, where one is given). Rows past ``n`` (past its tile,
    to be exact) are not written: what they hold is no number to use.

    :param src: (tokens, width), :func:`takes_shape`.
    :param tok: (rows,) int32, each in ``[0, tokens)`` (past ``n`` too).
    :param n: int32 scalar, the rows to visit.
    :param weights, partner: (rows,) float32 and (rows, width), both or
        neither. With them the rows are ``weights[r] * src[tok[r]]`` and
        the second result is ``<partner[r], src[tok[r]]>`` (rows,)
        float32, the gradient of an :func:`add_rows` in its weights.
    """
    weighted = weights is not None
    (tokens, width), rows = src.shape, tok.shape[0]
    sub = width // _LANES
    vector, block, dots, resident = _specs(tile, width, tokens)
    slabs = src.astype(_F32).reshape(tokens * sub, _LANES)
    tok = pad_to(tok, tile, 0)[0]
    out_shape = jax.ShapeDtypeStruct(
        (tok.shape[0], width), partner.dtype if weighted else src.dtype)
    common = dict(tokens=tokens, width=width, tile=tile, interpret=interpret)
    if not weighted:
        return _call(
            _make_take_kernel(tile, sub, False), "sparkdl_moe_take", n,
            (tok, slabs), [vector, resident], out_shape, block, scratch=1,
            **common)[:rows]
    # a tile's dots as a row of lanes, (1, tile) of (tiles, 1, tile)
    out, dotted = _call(
        _make_take_kernel(tile, sub, True), "sparkdl_moe_take", n,
        (tok, pad_to(weights.astype(_F32), tile, 0)[0], slabs,
         pad_to(partner, tile, 0)[0]),
        [vector, vector, resident, block],
        (out_shape, jax.ShapeDtypeStruct(
            (tok.shape[0] // tile, 1, tile), _F32)),
        (block, dots), scratch=2, **common)
    return out[:rows], dotted.reshape(-1)[:rows]


@functools.partial(jax.jit, inline=True,
                   static_argnames=("tokens", "tile", "interpret"))
def add_rows(y, tok, weights, n, *, tokens, tile=ROWS_TILE, interpret=False):
    """``out[t] = sum over r < n with tok[r] == t of weights[r] * y[r]``,
    (tokens, width) float32. Rows of ``y`` past ``n`` are not read.

    :param y: (rows, width), :func:`takes_shape`.
    :param tok: (rows,) int32, each in ``[0, tokens)``.
    :param weights: (rows,) float32.
    :param n: int32 scalar, the rows to visit.
    """
    width = y.shape[1]
    sub = width // _LANES
    vector, block, _, resident = _specs(tile, width, tokens)
    out = _call(
        _make_add_kernel(tile, sub), "sparkdl_moe_add", n,
        (pad_to(tok, tile, 0)[0], pad_to(weights.astype(_F32), tile, 0)[0],
         pad_to(y, tile, 0)[0]),
        [vector, vector, block],
        jax.ShapeDtypeStruct((tokens * sub, _LANES), _F32), resident,
        scratch=1, tokens=tokens, width=width, tile=tile, interpret=interpret)
    return out.reshape(tokens, width)
