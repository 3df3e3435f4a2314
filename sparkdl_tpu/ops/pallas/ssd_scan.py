"""Pallas TPU kernels of the chunked state-space scan (Mamba-2, SSD),
forward and backward: ``sparkdl_ssd_fwd`` and ``sparkdl_ssd_bwd`` (the
names their events take in a device trace). The mathematics is
:func:`sparkdl_tpu.ops.ssd.ssd_chunked`'s; what this file decides is
where the numbers live.

- **One program is one chunk of one block of heads** (at most a
  group's, which share ``B`` and ``C``; :func:`ssd_blocks` picks how
  many from the shape). Blocks come straight from ``x`` as (batch, seq,
  heads x head_dim) and ``B`` / ``C`` as (batch, seq, groups x state):
  a chunk's rows, a block's columns. Only ``dt`` (heads numbers a
  step) is handed over transposed, (batch, heads, seq), so that a
  head's steps are a lane-dense row.
- **A chunk's decay never leaves VMEM.** ``C B^T`` once a program; the
  cumulative log-decay of the chunk as ONE product with a triangular
  ones matrix; then a head at a time ``exp(cum_i - cum_j)``, masked,
  times the scores and ``dt_j``, cast, times ``x``. The decay is
  computed from the DIFFERENCE of the sums: at ``A = -16, dt = 0.1`` a
  chunk's sum reaches -200 and ``exp(cum_i) * exp(-cum_j)`` overflows.
- **The state is carried on the chip**: the chunk axis is the grid's
  last, ``"arbitrary"`` axis and the block's state, (state, heads x
  head_dim) float32, lives in VMEM scratch across it. ``C`` times the
  entering state and ``B^T`` times the decayed ``x`` are each one
  product over all the block's columns.
- **The backward walks the chunks in reverse** with the state's
  cotangent in the same scratch, rebuilds the chunk's decay from ``dt``
  and ``A``, and works on the TRANSPOSED (j, i) tile, so that every
  per-head product is in natural form. What it needs of the forward is
  the state entering each chunk, which the forward writes (in the
  inputs' dtype: every product that reads it takes it so) only when a
  backward will follow.
- A head of 64 is half a vreg's lanes: heads are handled a 128-lane
  group at a time, each head's product taken over the whole group and
  kept where a lane mask says it is the head's own. No slice, load or
  store is off the (8, 128) tiling.

Products take their operands in the inputs' dtype and accumulate in
float32; decays, cumulative sums, the carried state and the per-step
gradients (``dt``, ``A``) are float32. Sums over a head's columns are
products with a 0/1 matrix under the same rule.
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

_LANES = 128
# as ops/pallas/flash_attention.py: three quarters of a v5e's 16 MiB
# of scoped VMEM a kernel, reckoned from blocks, scratch and the
# column-wide temporaries
VMEM_BUDGET = 12 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a^T @ b
_F32 = jnp.float32
_EXACT = jax.lax.Precision.HIGHEST


class SsdBlocks(NamedTuple):
    """``heads`` a program, handled ``lane_heads`` at a time (one lane
    group of ``lane_heads * head_dim`` columns), ``vmem_bytes``
    reckoned for the backward kernel, the larger."""

    heads: int
    lane_heads: int
    vmem_bytes: int


def _vmem_bytes(hb, p, n, chunk, itemsize):
    """VMEM one program of the backward needs: pipelined blocks twice,
    scratch once, and the temporaries as wide as the block."""
    cols = hb * p
    wide, square, rows = chunk * cols, chunk * chunk, max(hb, 8) * chunk * 4
    blocks = (3 * wide * itemsize            # x, dy in; dx out
              + n * cols * itemsize          # the state entering
              + 4 * chunk * n * itemsize     # B, C in; dB, dC out
              + 3 * rows)                    # dt in; ddt, da out
    scratch = n * cols * 4 + 2 * wide * itemsize + rows
    # C h, B dh in float32; the state and its cotangent cast; the new
    # cotangent; a head's (chunk, chunk) tiles
    temporaries = (2 * wide * 4 + 2 * n * cols * itemsize + n * cols * 4
                   + 10 * square * 4)
    return 2 * blocks + scratch + temporaries


def ssd_blocks(heads, head_dim, groups, state, chunk, itemsize,
               tiled=True):
    """How many heads a program takes at (heads, head_dim, groups,
    state, chunk) with operands of `itemsize` bytes: a pure function of
    the shape, or None where the kernels do not take the shape.

    The most heads of ONE group (they share a program's ``C B^T``)
    whose blocks lie on the (8, 128) tiling and whose reckoned VMEM
    stays under ``VMEM_BUDGET``. Two groups never share a program: a
    program's products are already as wide as its columns. `tiled`
    False (the interpreter, tests) asks for no tiling and takes a whole
    group.
    """
    a_group = heads // groups
    if not tiled:
        return SsdBlocks(a_group, a_group, _vmem_bytes(
            a_group, head_dim, state, chunk, itemsize))
    if chunk % _LANES or state % _LANES:
        return None
    if head_dim % _LANES == 0:
        lane_heads = 1
    elif _LANES % head_dim == 0:
        lane_heads = _LANES // head_dim
    else:
        return None
    for hb in range(a_group, 0, -1):
        # a block of dt is (hb, chunk): whole sublane tiles, or all heads
        if a_group % hb or hb % lane_heads or (hb % 8 and hb != heads):
            continue
        need = _vmem_bytes(hb, head_dim, state, chunk, itemsize)
        if need <= VMEM_BUDGET:
            return SsdBlocks(hb, lane_heads, need)
    return None


def _dot(a, b, dims=_NN):
    """A product in the operands' dtype, accumulated in float32;
    float32 operands (tests, and the kernel's own float32 sums) are
    multiplied as float32, not as one bf16 pass."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=_EXACT if a.dtype == _F32 else None)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _head_columns(hb, p, dtype):
    """(hb, hb x p) 0/1: row r is 1 on head r's columns. Times a
    (rows, hb x p) array transposed, it sums each head's columns; a
    (hb, 1) column times it, summed over the rows, repeats a number a
    head over the head's columns."""
    col, row = _iota((hb, hb * p), 1), _iota((hb, hb * p), 0)
    return ((col >= row * p) & (col < (row + 1) * p)).astype(dtype)


def _steps(dt_ref, a_ref, chunk):
    """A chunk's per-step numbers as (heads, chunk) rows, float32:
    ``dt``, the log-decay summed from the chunk's start up to and with
    each step, its total, and ``exp(total - cum)``."""
    dt = dt_ref[0]
    upto = (_iota((chunk, chunk), 0) <= _iota((chunk, chunk), 1)).astype(_F32)
    cum = _dot(dt * a_ref[...], upto)
    total = cum[:, chunk - 1:]
    return dt, cum, total, jnp.exp(total - cum)


def _columns(rows, chunk):
    """(k, chunk) rows as (chunk, k) columns: a product with the
    identity, which the MXU does in any layout."""
    eye = (_iota((chunk, chunk), 0) == _iota((chunk, chunk), 1)).astype(_F32)
    return _dot(eye, rows, _NT)


def _over_lanes(columns, heads, p, width):
    """(chunk, width): head k's (chunk, 1) column of `columns` repeated
    over the head's `p` lanes of a lane group."""
    out = jnp.broadcast_to(columns[:, heads[0]:heads[0] + 1],
                           (columns.shape[0], width))
    lane = _iota(out.shape, 1)
    for k, head in enumerate(heads[1:], 1):
        out = jnp.where(lane >= k * p, columns[:, head:head + 1], out)
    return out


def _own(k, p, shape):
    """Lanes of a lane group that are its k-th head's."""
    lane = _iota(shape, 1)
    return (lane >= k * p) & (lane < (k + 1) * p)


def _make_fwd_kernel(t, p, chunk, save_states):
    from jax.experimental import pallas as pl

    hb, per = t.heads, t.lane_heads
    width = per * p

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, *rest):
        states_ref = rest[0] if save_states else None
        state, decayed = rest[-2:]
        dtype = x_ref.dtype

        @pl.when(pl.program_id(2) == 0)
        def _():
            state[...] = jnp.zeros_like(state)

        entering = state[...]
        if save_states:
            states_ref[0, 0] = entering.astype(states_ref.dtype)
        dt, cum, total, to_end = _steps(dt_ref, a_ref, chunk)
        cols = _columns(jnp.concatenate([cum, to_end * dt, dt], axis=0), chunk)
        bm, cm = b_ref[0], c_ref[0]
        scores = _dot(cm, bm, _NT)                         # (i, j)
        read = _dot(cm, entering.astype(dtype))            # (chunk, cols)
        causal = _iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
        for lg in range(hb // per):
            heads = range(lg * per, (lg + 1) * per)
            at = slice(lg * width, (lg + 1) * width)
            x = x_ref[0, :, at]
            y = None
            for k, r in enumerate(heads):
                seg = cols[:, r:r + 1] - cum[r:r + 1]
                decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
                weights = (scores * decay * dt[r:r + 1]).astype(dtype)
                y_r = _dot(weights, x)
                y = y_r if y is None else jnp.where(
                    _own(k, p, y_r.shape), y_r, y)
            y = (y + read[:, at] * jnp.exp(_over_lanes(cols, heads, p, width))
                 + x.astype(_F32) * d_ref[:, at])
            y_ref[0, :, at] = y.astype(y_ref.dtype)
            to_end_x = _over_lanes(cols, [hb + r for r in heads], p, width)
            decayed[:, at] = (x.astype(_F32) * to_end_x).astype(dtype)
        keep = jnp.sum(_head_columns(hb, p, _F32) * jnp.exp(total), axis=0,
                       keepdims=True)
        state[...] = entering * keep + _dot(bm, decayed[...], _TN)

    return kernel


def _make_bwd_kernel(t, p, chunk):
    from jax.experimental import pallas as pl

    hb, per = t.heads, t.lane_heads
    width = per * p

    def kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref,
               states_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
               dstate, dread, decayed, sums):
        dtype = x_ref.dtype

        @pl.when(pl.program_id(2) == 0)
        def _():
            dstate[...] = jnp.zeros_like(dstate)

        leaving = dstate[...]                  # cotangent of the state out
        entering = states_ref[0, 0]
        dt, cum, total, to_end = _steps(dt_ref, a_ref, chunk)
        cols = _columns(jnp.concatenate([cum, to_end * dt, dt], axis=0),
                        chunk)
        bm, cm = b_ref[0], c_ref[0]
        scores_t = _dot(bm, cm, _NT)                       # (j, i)
        read = _dot(cm, entering.astype(dtype))            # C h
        dx_decayed = _dot(bm, leaving.astype(dtype))       # B dh
        causal_t = _iota((chunk, chunk), 0) <= _iota((chunk, chunk), 1)
        dscores_t = jnp.zeros((chunk, chunk), _F32)
        ones = jnp.ones((8, chunk), dtype)
        x_v, x_bdh, dy_read = [], [], []
        for lg in range(hb // per):
            heads = range(lg * per, (lg + 1) * per)
            at = slice(lg * width, (lg + 1) * width)
            x, dy = x_ref[0, :, at], dy_ref[0, :, at]
            v = None
            for k, r in enumerate(heads):
                own = _own(k, p, x.shape)
                seg_t = cum[r:r + 1] - cols[:, r:r + 1]
                # d weights (j, i) = x_j . dy_i over the head's columns
                dweights_t = _dot(jnp.where(own, x, jnp.zeros_like(x)), dy,
                                  _NT)
                decay_t = jnp.exp(jnp.where(causal_t, seg_t, -jnp.inf))
                v_r = _dot((scores_t * decay_t).astype(dtype), dy)
                through = dweights_t * (
                    decay_t * cols[:, 2 * hb + r:2 * hb + r + 1])
                v = v_r if v is None else jnp.where(own, v_r, v)
                dscores_t = dscores_t + through
                # what the decay's exponent receives, (j, i), summed
                # over j and over i FROM ONE rounded tile: d cum is the
                # difference of the two and the diagonal, their largest
                # term, has to cancel
                dseg_t = (through * scores_t).astype(dtype)
                sums[r:r + 1, :] = _dot(ones, dseg_t)[:1]
                sums[hb + r:hb + r + 1, :] = _dot(ones, dseg_t, _NT)[:1]
            x32, dy32 = x.astype(_F32), dy.astype(_F32)
            to_end_x = _over_lanes(cols, [hb + r for r in heads], p, width)
            dx = (v * _over_lanes(cols, [2 * hb + r for r in heads], p, width)
                  + dx_decayed[:, at] * to_end_x + dy32 * d_ref[:, at])
            dx_ref[0, :, at] = dx.astype(dx_ref.dtype)
            dread[:, at] = (dy32 * jnp.exp(_over_lanes(
                cols, heads, p, width))).astype(dtype)
            decayed[:, at] = (x32 * to_end_x).astype(dtype)
            x_v.append((x32 * v).astype(dtype))
            x_bdh.append((x32 * dx_decayed[:, at]).astype(dtype))
            dy_read.append((dy32 * read[:, at]).astype(dtype))

        # sums over each head's columns, as (heads, chunk) rows
        own_t = _head_columns(hb, p, dtype)
        x_v, x_bdh, dy_read = (
            _dot(own_t, jnp.concatenate(a, axis=1), _NT)
            for a in (x_v, x_bdh, dy_read))
        direct = x_v + x_bdh * to_end        # d dt but through the decay
        own32 = _head_columns(hb, p, _F32)
        keep = jnp.exp(total)
        # the chunk's total reaches the state that leaves and, through
        # exp(total - cum), what each step adds to it
        dtotal = (keep * jnp.sum(
            own32 * jnp.sum(leaving * entering.astype(_F32), axis=0,
                            keepdims=True), axis=1, keepdims=True)
            + jnp.sum(x_bdh * to_end * dt, axis=1, keepdims=True))
        dcum = (sums[:hb] - sums[hb:] - dt * x_bdh * to_end
                + jnp.exp(cum) * dy_read
                + jnp.where(_iota(cum.shape, 1) == chunk - 1, dtotal, 0.0))
        onward = (_iota((chunk, chunk), 0) >= _iota((chunk, chunk), 1)
                  ).astype(_F32)
        da = _dot(dcum, onward)              # sum over the steps from k on
        da_ref[0] = da
        ddt_ref[0] = direct + da * a_ref[...]
        dread_all, decayed_all = dread[...], decayed[...]
        db_ref[0] = (
            _dot(dscores_t.astype(dtype), cm)
            + _dot(decayed_all, leaving.astype(dtype), _NT)
        ).astype(db_ref.dtype)
        dc_ref[0] = (
            _dot(dscores_t.T.astype(dtype), bm)
            + _dot(dread_all, entering.astype(dtype), _NT)
        ).astype(dc_ref.dtype)
        dstate[...] = (leaving * jnp.sum(own32 * keep, axis=0, keepdims=True)
                       + _dot(cm, dread_all, _TN))

    return kernel


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _specs(pl, t, p, n, chunk, a_group, chunk_of):
    """Blocks of (x, dt, A, B, C, D) for grid step (batch, block of
    heads, step): chunk ``chunk_of(step)``, a block's columns, its
    group's B and C."""
    cols = t.heads * p
    per_group = a_group // t.heads
    return [
        pl.BlockSpec((1, chunk, cols), lambda b, j, c: (b, chunk_of(c), j)),
        pl.BlockSpec((1, t.heads, chunk), lambda b, j, c: (b, j, chunk_of(c))),
        pl.BlockSpec((t.heads, 1), lambda b, j, c: (j, 0)),
        pl.BlockSpec((1, chunk, n),
                     lambda b, j, c: (b, chunk_of(c), j // per_group)),
        pl.BlockSpec((1, chunk, n),
                     lambda b, j, c: (b, chunk_of(c), j // per_group)),
        pl.BlockSpec((1, cols), lambda b, j, c: (0, j)),
    ]


# Jitted INLINE, as the flash kernels' entry points are: the program is
# the same, but a kernel body is traced once a shape and not once a
# layer (a remat step of five state-space layers calls these fifteen
# times).
_STATIC = ("heads", "groups", "chunk", "blocks", "interpret")


@functools.partial(jax.jit, inline=True,
                   static_argnames=_STATIC + ("save_states",))
def ssd_scan_fwd(x, dt_t, a, b, c, d_cols, *, heads, groups, chunk, blocks,
                 save_states=False, interpret=False):
    """``y`` (batch, seq, heads x head_dim) and, with `save_states`,
    the state entering each chunk, (batch, chunks, state, heads x
    head_dim) in ``x``'s dtype.

    :param x: (batch, seq, heads x head_dim), seq a multiple of `chunk`.
    :param dt_t: (batch, heads, seq) float32.
    :param a: (heads, 1) float32.
    :param b, c: (batch, seq, groups x state).
    :param d_cols: (1, heads x head_dim) float32, the skip a column.
    :param blocks: :func:`ssd_blocks`'s answer for the shape.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, inner = x.shape
    p, n = inner // heads, b.shape[2] // groups
    cols, n_chunks = blocks.heads * p, s // chunk
    out_shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
    out_specs = pl.BlockSpec((1, chunk, cols), lambda bi, j, ci: (bi, ci, j))
    if save_states:
        out_shape = (out_shape, jax.ShapeDtypeStruct(
            (batch, n_chunks, n, inner), x.dtype))
        out_specs = (out_specs, pl.BlockSpec(
            (1, 1, n, cols), lambda bi, j, ci: (bi, ci, 0, j)))
    return pl.pallas_call(
        _make_fwd_kernel(blocks, p, chunk, save_states),
        out_shape=out_shape,
        grid=(batch, heads // blocks.heads, n_chunks),
        in_specs=_specs(pl, blocks, p, n, chunk, heads // groups,
                        lambda ci: ci),
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, cols), _F32),
                        pltpu.VMEM((chunk, cols), x.dtype)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="sparkdl_ssd_fwd",
    )(x, dt_t, a, b, c, d_cols)


@functools.partial(jax.jit, inline=True, static_argnames=_STATIC)
def ssd_scan_bwd(x, dt_t, a, b, c, d_cols, dy, states, *, heads, groups,
                 chunk, blocks, interpret=False):
    """``(dx, ddt_t, da_t, db, dc)`` from what the forward took, ``y``'s
    cotangent and the forward's saved states. ``ddt_t`` and ``da_t``
    (the cotangent of ``dt * A`` a step) are (batch, heads, seq)
    float32; ``db`` and ``dc`` are (batch, seq, blocks of heads x
    state), a group's blocks still to be added up.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    batch, s, inner = x.shape
    p, n = inner // heads, b.shape[2] // groups
    cols, n_chunks = blocks.heads * p, s // chunk
    n_blocks = heads // blocks.heads

    def back(ci):
        return n_chunks - 1 - ci

    wide = pl.BlockSpec((1, chunk, cols), lambda bi, j, ci: (bi, back(ci), j))
    rows = pl.BlockSpec(
        (1, blocks.heads, chunk), lambda bi, j, ci: (bi, j, back(ci)))
    group = pl.BlockSpec((1, chunk, n), lambda bi, j, ci: (bi, back(ci), j))
    steps = jax.ShapeDtypeStruct(dt_t.shape, _F32)
    shared = jax.ShapeDtypeStruct((batch, s, n_blocks * n), b.dtype)
    return pl.pallas_call(
        _make_bwd_kernel(blocks, p, chunk),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype), steps, steps,
                   shared, shared),
        grid=(batch, n_blocks, n_chunks),
        in_specs=_specs(pl, blocks, p, n, chunk, heads // groups, back) + [
            wide,
            pl.BlockSpec((1, 1, n, cols),
                         lambda bi, j, ci: (bi, back(ci), 0, j))],
        out_specs=(wide, rows, rows, group, group),
        scratch_shapes=[pltpu.VMEM((n, cols), _F32),
                        pltpu.VMEM((chunk, cols), x.dtype),
                        pltpu.VMEM((chunk, cols), x.dtype),
                        pltpu.VMEM((2 * blocks.heads, chunk), _F32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="sparkdl_ssd_bwd",
    )(x, dt_t, a, b, c, d_cols, dy, states)
