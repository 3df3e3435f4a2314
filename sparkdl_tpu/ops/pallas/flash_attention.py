"""Pallas TPU flash attention, forward and backward, tiled for the MXU.

The hot op of every transformer in the model zoo. Three kernels, named
``sparkdl_flash_fwd``, ``sparkdl_flash_dq`` and ``sparkdl_flash_dkv``
(the names their events take in a device trace). The algorithm is the
standard one: an online softmax over K/V tiles in the forward, which
saves only the per-row logsumexp, and a backward that recomputes the
probabilities tile by tile from (q, k, lse). Scores never reach HBM, so
memory is O(S·D). What this file decides is how that work is laid out
for the chip (what each choice bought on a v5e is in PERF.md, PR 25):

- **Tiles come from the shape.** :func:`flash_tiles` picks ``(bq, bk)``
  for each kernel from ``(S, head_dim, itemsize)``: several MXU passes
  deep (512 x 512 where the sequence allows, not 128 x 128), so that
  fill and drain of the systolic array, the loop and the softmax
  bookkeeping are paid once a large tile. The stationary side of a
  kernel (q for the forward and dq, k/v for dk/dv) is one tile of a
  grid ``(batch, heads, tiles, major blocks)``; the streamed side comes
  in *major* blocks, as much of the sequence as the reckoned VMEM
  allows, and the kernel walks a major block in tile-wide steps. The
  VMEM the rule plans with is what the kernel then ASKS Mosaic for
  (``vmem_limit_bytes``, only past what the default scope holds), so
  through S = 32768 at head size 128 and S = 16384 at 256 that is all
  of the sequence: one grid step a tile, the state in the loop's
  carry, no scratch, K/V (or q/do) of a head fetched once. On a v5e at
  S = 8192 that took a third off the forward and a tenth to a third
  off the backward against major blocks of 1024-4096 under the default
  scope, with the same bits out (PERF.md, PR 36). Past the ceiling the
  fourth grid axis carries the state in VMEM scratch, so VMEM does not
  grow with S, and an index map that stops at the diagonal keeps
  blocks no row can see from being fetched at all.
- **The causal mask is applied on the diagonal only.** A walk is ONE
  loop over the tiles wholly below the diagonal (no iota, no compare,
  no select), then the tiles that cross it, unrolled, with the mask;
  tiles wholly above are never visited. (A second loop for the
  crossing tiles cost more than masking every tile did.)
  :func:`_causal_keep` is the one definition of visibility.
- **The scale is folded into an operand**: into q once a program in the
  forward and dq, into the resident k in dk/dv, and into dq / dk once
  after the walk; never into a score tile.
- **Softmax state stays two-dimensional.** The running maximum, ``lse``
  and ``delta`` are ``(bq, 1)`` columns in the q-stationary kernels;
  the running sum is kept as 128 lane-partial sums a row (VPU adds a
  tile, one cross-lane reduce a program). dk/dv computes the
  TRANSPOSED score tile ``k @ q^T``, so every matmul is in natural
  form (no transpose of a probability tile) and ``lse`` / ``delta``
  are read as lane-dense ``(1, bq)`` rows. At the function boundary
  ``lse`` is ``(B, H, S, 1)`` float32, which is what ring-flash merges
  by.
- bf16 operands into every matmul, fp32 accumulators
  (``preferred_element_type``) and fp32 softmax state.

A causal row always sees key 0, which is in the first tile walked, so
the running maximum is finite from the first tile on and the causal
forward needs no fully-masked-row guard. Under a window that no longer
holds: the first tile walked crosses the window's far edge and its
later rows see nothing in it, so their running maximum is still
``NEG_INF`` and ``exp(s - m)`` reads 1 where it should read 0. The
windowed forward therefore masks its probabilities too, as dq and
dk/dv always have (a row sees itself, so its state is finite by the
last tile walked).
"""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from sparkdl_tpu import observe

NEG_INF = -1e30
_LANES = 128
# A v5e core has 128 MiB of VMEM, of which Mosaic scopes 16 MiB to a
# kernel unless the kernel asks for more. The rule lets a kernel reckon
# three eighths of the core's VMEM. The reckoning counts pipelined
# blocks, scratch and the tile-sized temporaries, not what the compiler
# keeps besides (compiled for a v5e, the kernels took 0.9 to 1.1 of
# it), so a kernel whose reckoning is past three quarters of the
# default scope (`_UNASKED_BYTES`, all the rule allowed before PR 36)
# asks for the reckoning and a third more (`FlashTiles.vmem_limit`: at
# most half of the core's VMEM). Under that it asks for nothing and is
# the program it was. The ceiling is from a v5e (PERF.md, PR 36): at
# S = 8192, 16384 and 32768 and head sizes 128 and 256 every kernel
# was faster the more of the streamed side it held, up to a reckoning
# of 76.5 MiB; the ceiling admits the whole of S = 32768 at head size
# 128 (dk/dv reckons 42.5 MiB) and of S = 8192 at 256 (25.5 MiB), the
# cells' shapes among them, and leaves the other half of VMEM alone.
VMEM_BUDGET = 48 * 2 ** 20
_UNASKED_BYTES = 12 * 2 ** 20
# Rows of the stationary tile and width of a step, for every kernel:
# 512 x 512 was the fastest of 128-2048 x 128-2048 for each of the
# three on a v5e at (4, 32, 2048, 128) (PERF.md, PR 25).
_TARGET_TILE = 512
_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _causal_keep(q_start, k_start, bq, bk, transposed=False, window=None):
    """Block-local visibility mask: causal (q_pos >= k_pos) and, with
    `window`, no further back than it (q_pos - k_pos < window). Shared
    by the forward and both backward kernels so masking semantics can
    never diverge between them. ``(bq, bk)``, or ``(bk, bq)`` for the
    transposed score tile of dk/dv."""
    shape, q_axis = ((bk, bq), 1) if transposed else ((bq, bk), 0)
    # how far a key is ahead of a query inside the tile is the same in
    # every tile (the compiler keeps it out of the walk); where the
    # tile lies is one scalar
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, shape, q_axis))
    keep = ahead <= q_start - k_start
    if window is not None:
        keep = jnp.logical_and(keep, ahead > q_start - k_start - window)
    return keep


class FlashTiles(NamedTuple):
    """One kernel's tiling: ``bq`` x ``bk`` score tiles, the streamed
    side fetched ``major`` rows at a time, ``vmem_bytes`` reckoned."""

    bq: int
    bk: int
    major: int
    vmem_bytes: int

    @property
    def vmem_limit(self):
        """The ``vmem_limit_bytes`` the kernel asks Mosaic for: the
        reckoned need and a third more, or 0 (nothing asked) where the
        need is within what the default scope was always trusted with."""
        if self.vmem_bytes <= _UNASKED_BYTES:
            return 0
        return self.vmem_bytes + self.vmem_bytes // 3


def _round_up(x, m):
    return -(-x // m) * m


def _vmem_bytes(kernel, bq, bk, major, d, itemsize):
    """VMEM one program of `kernel` needs: pipelined blocks twice
    (double-buffered), scratch once, and the score-tile temporaries. A
    block narrower than 128 lanes or 8 sublanes is padded to them."""
    dl = _round_up(d, _LANES)
    column = bq * _LANES * 4     # (bq, 1) or (bq, 128) float32
    # s, p (and dp, ds in the backward) in float32 plus the matmul
    # operand cast of p / ds
    n_f32 = 2 if kernel == "fwd" else 4
    temporaries = bq * bk * (4 * n_f32 + itemsize)
    if kernel == "fwd":
        blocks = 2 * bq * dl * itemsize + 2 * major * dl * itemsize + column
        scratch = 2 * column + bq * dl * 4
    elif kernel == "dq":
        blocks = (3 * bq * dl * itemsize + 2 * major * dl * itemsize
                  + 2 * column)
        scratch = bq * dl * 4
    else:
        blocks = (4 * bk * dl * itemsize + 2 * major * dl * itemsize
                  + 2 * 8 * _round_up(major, _LANES) * 4)
        scratch = 2 * bk * dl * 4
    return 2 * blocks + scratch + temporaries + 2 * max(bq, bk) * dl * 4


def flash_tiles(kernel, s, d, itemsize, bq=None, bk=None):
    """Tiles of `kernel` (``"fwd"``, ``"dq"``, ``"dkv"``) for a sequence
    of `s` as the kernel gets it (``ops.attention`` pads to a multiple
    of 128 where the rule is to choose), head size `d` and operands of
    `itemsize` bytes: a pure function of the shape.

    ``bq`` / ``bk`` given are kept as they are (clamped to the
    sequence); left out, each is the largest power-of-two share of
    ``_TARGET_TILE`` that divides the sequence. The streamed side then
    comes in the largest major block, a multiple of its tile that
    divides the sequence, whose reckoned VMEM stays under
    ``VMEM_BUDGET``: the whole sequence where that fits (the tiles are
    not halved to make it fit: 512 x 512 whole beat 256 x 512 whole in
    every kernel; PERF.md, PR 36). Tiles the rule chose halve only
    where not even one tile a block fits.
    """
    fit = min(_TARGET_TILE, s)
    while s % fit:
        fit //= 2
    by_rule = (bq is None, bk is None)
    bq = fit if bq is None else min(bq, s)
    bk = fit if bk is None else min(bk, s)
    if s % bq or s % bk:
        raise ValueError(f"seq {s} must be divisible by bq={bq}, bk={bk}")
    if max(bq, bk) % min(bq, bk):
        raise ValueError(
            f"flash tiles must nest (one of bq={bq}, bk={bk} divides the "
            "other): the tiles on the diagonal are counted from it")
    while True:
        step = max(bq, bk)       # a major block holds whole tiles of both
        n = s // step
        for parts in range(1, n + 1):
            if n % parts:
                continue
            major = s // parts
            need = _vmem_bytes(kernel, bq, bk, major, d, itemsize)
            if need <= VMEM_BUDGET:
                return FlashTiles(bq, bk, major, need)
        # not even one tile a block fits: halve the larger tile the
        # rule chose; explicit tiles are the caller's to answer for
        if by_rule[0] and (bq >= bk or not by_rule[1]) and bq > _LANES:
            bq //= 2
        elif by_rule[1] and bk > _LANES:
            bk //= 2
        else:
            return FlashTiles(bq, bk, step, need)


def _window(window, causal, s):
    """The window the kernels are built with: None where it covers the
    sequence (that IS the causal kernel)."""
    if window is None:
        return None
    if not causal or window < 1:
        raise ValueError(
            f"window={window}: a window is causal and at least 1 wide")
    return None if window >= s else int(window)


def tiles_walked(kernel, s, tiles, window=None):
    """Score tiles one (batch, head) of `kernel`'s causal walk visits
    over a sequence of `s`, by the bounds the kernels walk by: every
    tile with a visible pair, none else."""
    dkv = kernel == "dkv"
    stationary, streamed = (tiles.bk, tiles.bq) if dkv else (
        tiles.bq, tiles.bk)
    total = 0
    for start in range(0, s, stationary):
        if dkv:     # query tiles from the diagonal to the window's edge
            first, last = start // streamed, s // streamed - 1
            if window is not None:
                last = min(last,
                           (start + stationary + window - 2) // streamed)
        else:       # key tiles from the window's edge to the diagonal
            last = (start + stationary - 1) // streamed
            first = 0 if window is None else max(
                start - window + 1, 0) // streamed
        total += last - first + 1
    return total


def _count_tiles(kernel, s, d, tiles, explicit, window=None):
    """``flash.tiles``: once a kernel a traced call, which tiles the
    step was built with and who chose them, the window (0: none) and
    the tiles a (batch, head) walks beside the causal walk's."""
    if not observe.enabled():
        return
    observe.inc("flash.tiles", kernel=kernel, s=s, d=d, bq=tiles.bq,
                bk=tiles.bk, major=tiles.major,
                vmem_limit=tiles.vmem_limit,
                chosen="argument" if explicit else "rule",
                window=window or 0,
                tiles_walked=tiles_walked(kernel, s, tiles, window),
                tiles_causal=tiles_walked(kernel, s, tiles))


def _scaled(x, scale):
    """`x` times the softmax scale in `x`'s own dtype, rounded once."""
    if scale == 1.0:
        return x
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _lane_sums_width(bk):
    return _LANES if bk % _LANES == 0 else 1


def _lane_sums(p):
    """Row sums of `p` left one step short: where the tile is whole
    vregs wide, its 128-lane column groups added up elementwise (VPU
    adds, no cross-lane reduce a tile); the lanes are summed once, when
    the walk is over. A narrower tile (tests) sums its rows outright."""
    bk = p.shape[-1]
    if _lane_sums_width(bk) == 1:
        return jnp.sum(p, axis=-1, keepdims=True)
    sums = p[:, :_LANES]
    for c in range(1, bk // _LANES):
        sums = sums + p[:, c * _LANES:(c + 1) * _LANES]
    return sums


def _walk(tile, carry, first_cross, n_cross, n_tiles, *, causal,
          cross_first, whole, edge=None):
    """Run ``tile(j, carry, masked)`` over one major block's tiles: the
    tiles wholly below the diagonal in ONE loop without a mask, and
    the `n_cross` tiles that cross it, from tile `first_cross` on,
    unrolled with one (a second loop costs more than it saves: PERF.md,
    PR 25). Tiles above the diagonal are not visited. Without `causal`
    every tile is plain. `cross_first` is dk/dv's order (the diagonal
    is at the low end of its walk over q tiles); `whole` says the major
    block is the whole sequence, so the diagonal is in it.

    `edge` is a window's far side, two tile indices in walking order
    (either may lie outside the block): with the diagonal last, the
    first tile with a visible pair and the first wholly inside the
    window; with it first, the first tile not wholly inside and the
    first with no visible pair. The tiles between the two run masked,
    in a loop of their own; what lies beyond is not visited."""
    plain = functools.partial(tile, masked=False)
    if not causal:
        return jax.lax.fori_loop(0, n_tiles, plain, carry)
    masked = functools.partial(tile, masked=True)

    def cross(carry):
        for c in range(n_cross):
            carry = tile(first_cross + c, carry, masked=True)
        return carry

    def maybe_cross(carry):
        if whole:
            return cross(carry)
        here = jnp.logical_and(first_cross >= 0, first_cross < n_tiles)
        return jax.lax.cond(here, cross, lambda carry: carry, carry)

    if cross_first:
        carry = maybe_cross(carry)
        start = jnp.clip(first_cross + n_cross, 0, n_tiles)
        if edge is None:
            return jax.lax.fori_loop(start, n_tiles, plain, carry)
        inside, seen = (jnp.maximum(jnp.clip(e, 0, n_tiles), start)
                        for e in edge)
        carry = jax.lax.fori_loop(start, inside, plain, carry)
        return jax.lax.fori_loop(inside, seen, masked, carry)
    stop = jnp.clip(first_cross, 0, n_tiles)
    if edge is None:
        carry = jax.lax.fori_loop(0, stop, plain, carry)
    else:
        seen, inside = (jnp.minimum(jnp.clip(e, 0, n_tiles), stop)
                        for e in edge)
        carry = jax.lax.fori_loop(seen, inside, masked, carry)
        carry = jax.lax.fori_loop(inside, stop, plain, carry)
    return maybe_cross(carry)


def _keys_edge(q_start, k0, bq, bk, window):
    """`_walk`'s `edge` for a q-stationary kernel: of the key tiles of
    the major block at `k0`, the first a row of the q tile at `q_start`
    sees and the first that every row of it sees whole."""
    if window is None:
        return None
    return ((jnp.maximum(q_start - (window - 1), 0) - k0) // bk,
            (jnp.maximum(q_start + bq - window, 0) + bk - 1 - k0) // bk)


def _queries_edge(k_start, q0, bq, bk, window):
    """`_walk`'s `edge` for dk/dv: of the query tiles of the major
    block at `q0`, the first that no longer sees the whole key tile at
    `k_start` and the first that sees none of it."""
    if window is None:
        return None
    return ((k_start + window - q0) // bq,
            (k_start + bk + window - 2 - q0) // bq + 1)


def _across_major_blocks(pl, scratch, init, walk, finish):
    """``finish(walk(init))`` where the major block is the whole
    sequence (no scratch: a round trip through VMEM a program is a
    fifth of the forward's time at S = 2048; at S = 8192, with the
    grid steps it brings, a third). Else the fourth grid axis
    walks the major blocks in turn: the state starts as `init` on the
    first, crosses grid steps in `scratch`, and `finish` runs on the
    last."""
    if not scratch:
        return finish(walk(init))
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _():
        for ref, x in zip(scratch, init):
            ref[...] = x

    carry = walk(tuple(ref[...] for ref in scratch))
    for ref, x in zip(scratch, carry):
        ref[...] = x
    pl.when(j == pl.num_programs(3) - 1)(lambda: finish(carry))


def _make_kernel(t, causal, scale, with_lse=False, window=None):
    from jax.experimental import pallas as pl

    bq, bk = t.bq, t.bk
    n_tiles = t.major // bk

    def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
        lse_ref, scratch = (rest[0], rest[1:]) if with_lse else (None, rest)
        # Matmul INPUTS stay in the storage dtype (bf16 on TPU): the
        # MXU takes bf16 natively at full rate, while fp32 operands
        # run as multi-pass bf16 splits. fp32 happens where it
        # matters: the accumulators (preferred_element_type) and the
        # softmax state.
        q = _scaled(q_ref[0, 0], scale)                      # (bq, d)
        q_start, k0 = pl.program_id(2) * bq, pl.program_id(3) * t.major

        def tile(j, carry, masked):
            # m (bq, 1); l (bq, 128) lane-partial row sums; acc (bq, d)
            m, l, acc = carry
            start = pl.multiple_of(j * bk, bk)
            kb = k_ref[0, 0, pl.ds(start, bk), :]
            vb = v_ref[0, 0, pl.ds(start, bk), :]
            s = jax.lax.dot_general(
                q, kb, _NT, preferred_element_type=jnp.float32)
            if masked:
                keep = _causal_keep(q_start, k0 + start, bq, bk,
                                    window=window)
                s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if masked and window is not None:
                # a row that has seen nothing yet: exp(NEG_INF - NEG_INF)
                p = jnp.where(keep, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + _lane_sums(p)
            # p in [0,1] keeps full relative precision through the
            # bf16 cast; the accumulation stays fp32
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(vb.dtype), vb, _NN,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        def finish(carry):
            m, l, acc = carry
            l = jnp.maximum(jnp.sum(l, axis=-1, keepdims=True), 1e-30)
            o_ref[0, 0] = (acc * (1.0 / l)).astype(o_ref.dtype)
            if with_lse:
                # logsumexp per row: softmax probs are exp(s - lse) in
                # the backward. A (..., bq, 1) block: TPU tiling wants
                # the last two block dims (mult of 8, mult of 128 |
                # full dim), which a rank-3 (1, 1, bq) block is not.
                lse_ref[0, 0] = m + jnp.log(l)

        _across_major_blocks(
            pl, scratch,
            (jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, _lane_sums_width(bk)), jnp.float32),
             jnp.zeros((bq, q.shape[-1]), jnp.float32)),
            lambda carry: _walk(
                tile, carry, (q_start - k0) // bk, max(1, bq // bk),
                n_tiles, causal=causal, cross_first=False,
                whole=not scratch,
                edge=_keys_edge(q_start, k0, bq, bk, window)),
            finish)

    return kernel


def _tile_spec(pl, rows, width):
    """Blocks of the stationary side: tile `i` of the third grid axis,
    the same on every step of the fourth."""
    return pl.BlockSpec(
        (1, 1, rows, width), lambda bi, hi, i, j: (bi, hi, i, 0))


def _streamed_block(causal, tile, major, *, upto, window=None):
    """Which major block of a streamed operand grid step ``(i, j)``
    holds: block `j`, held at the last block the stationary tile `i`
    can see (`upto`: the forward and dq walk keys up to the diagonal)
    or at the first (dk/dv walks queries from it), and with a `window`
    at the block of its far edge too, so that a grid step with nothing
    to do fetches nothing."""
    if not causal:
        return lambda i, j: j
    if upto:
        last = lambda i: ((i + 1) * tile - 1) // major
        if window is None:
            return lambda i, j: jnp.minimum(j, last(i))
        return lambda i, j: jnp.clip(
            j, jnp.maximum(i * tile - (window - 1), 0) // major, last(i))
    first = lambda i: (i * tile) // major
    if window is None:
        return lambda i, j: jnp.maximum(j, first(i))
    return lambda i, j: jnp.minimum(
        jnp.maximum(j, first(i)), ((i + 1) * tile + window - 2) // major)


def _scratch(t, s, *shapes):
    """float32 VMEM scratch for the state that crosses major blocks;
    none where the one major block is the whole sequence."""
    from jax.experimental.pallas import tpu as pltpu

    if t.major == s:
        return []
    return [pltpu.VMEM(shape, jnp.float32) for shape in shapes]


def _compiler_params(t):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"),
        vmem_limit_bytes=t.vmem_limit or None,
    )


# Both entry points are jitted INLINE: the program is the same as
# without (no call in it), but JAX then traces a kernel once a shape
# and tiling, not once a layer. A 16-layer remat step calls them 64
# times, and tracing these kernel bodies anew each time is seconds of
# every start-up (PERF.md, PR 25).
@functools.partial(
    jax.jit, inline=True,
    static_argnames=("causal", "scale", "bq", "bk", "interpret",
                     "return_lse", "window"))
def flash_attention_bhsd(q, k, v, *, causal=True, scale=None, bq=None,
                         bk=None, interpret=False, return_lse=False,
                         window=None):
    """Flash attention on (batch, heads, seq, head_dim) arrays; with
    `window` (static) a query sees the `window` newest keys only.

    ``bq`` / ``bk`` left out are :func:`flash_tiles`'s; seq must be
    divisible by the tiles (the public wrapper in
    :mod:`sparkdl_tpu.ops.attention` pads). With ``return_lse`` also
    returns the per-row logsumexp (B, H, S, 1) for the fused backward
    (trailing singleton: see the tiling note in the kernel).
    """
    from jax.experimental import pallas as pl

    b, h, s, d = q.shape
    scale = scale or (d ** -0.5)
    window = _window(window, causal, s)
    t = flash_tiles("fwd", s, d, q.dtype.itemsize, bq, bk)
    _count_tiles("fwd", s, d, t, bq is not None or bk is not None, window)

    q_spec = _tile_spec(pl, t.bq, d)
    block = _streamed_block(causal, t.bq, t.major, upto=True, window=window)
    kv_spec = pl.BlockSpec(
        (1, 1, t.major, d),
        lambda bi, hi, i, j: (bi, hi, block(i, j), 0))
    lse_spec = _tile_spec(pl, t.bq, 1)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        )
    return pl.pallas_call(
        _make_kernel(t, causal, scale, with_lse=return_lse, window=window),
        out_shape=out_shape,
        grid=(b, h, s // t.bq, s // t.major),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(q_spec, lse_spec) if return_lse else q_spec,
        scratch_shapes=_scratch(
            t, s, (t.bq, 1), (t.bq, _lane_sums_width(t.bk)), (t.bq, d)),
        compiler_params=_compiler_params(t),
        interpret=interpret,
        name="sparkdl_flash_fwd",
    )(q, k, v)


def _make_dq_kernel(t, causal, scale, window=None):
    from jax.experimental import pallas as pl

    bq, bk = t.bq, t.bk
    n_tiles = t.major // bk

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch):
        # bf16 operands into every matmul (MXU-native rate), fp32
        # accumulators — see the forward kernel's dtype note.
        q = _scaled(q_ref[0, 0], scale)
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]                                 # (bq, 1)
        delta = delta_ref[0, 0]                             # (bq, 1)
        q_start, k0 = pl.program_id(2) * bq, pl.program_id(3) * t.major

        def tile(j, carry, masked):
            start = pl.multiple_of(j * bk, bk)
            kb = k_ref[0, 0, pl.ds(start, bk), :]
            vb = v_ref[0, 0, pl.ds(start, bk), :]
            s = jax.lax.dot_general(
                q, kb, _NT, preferred_element_type=jnp.float32)
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(
                    _causal_keep(q_start, k0 + start, bq, bk,
                                 window=window), p, 0.0)
            dp = jax.lax.dot_general(
                do, vb, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            return (carry[0] + jax.lax.dot_general(
                ds.astype(kb.dtype), kb, _NN,
                preferred_element_type=jnp.float32),)

        def finish(carry):
            dq_ref[0, 0] = (carry[0] * scale).astype(dq_ref.dtype)

        _across_major_blocks(
            pl, scratch, (jnp.zeros(q.shape, jnp.float32),),
            lambda carry: _walk(
                tile, carry, (q_start - k0) // bk, max(1, bq // bk),
                n_tiles, causal=causal, cross_first=False,
                whole=not scratch,
                edge=_keys_edge(q_start, k0, bq, bk, window)),
            finish)

    return kernel


def _make_dkv_kernel(t, causal, scale, window=None):
    from jax.experimental import pallas as pl

    bq, bk = t.bq, t.bk
    n_tiles = t.major // bq

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dk_ref, dv_ref, *scratch):
        # bf16 operands into every matmul (MXU-native rate), fp32
        # accumulators — see the forward kernel's dtype note. The
        # score tile is TRANSPOSED, (bk, bq): k @ q^T, p^T @ do,
        # v @ do^T and ds^T @ q are then all in natural form, and lse
        # and delta are rows that broadcast down the sublanes.
        kb = _scaled(k_ref[0, 0], scale)                    # (bk, d)
        vb = v_ref[0, 0]
        k_start, q0 = pl.program_id(2) * bk, pl.program_id(3) * t.major

        def tile(i, carry, masked):
            dk, dv = carry
            start = pl.multiple_of(i * bq, bq)
            qb = q_ref[0, 0, pl.ds(start, bq), :]
            dob = do_ref[0, 0, pl.ds(start, bq), :]
            lse = lse_ref[0, 0, :, pl.ds(start, bq)]        # (1, bq)
            delta = delta_ref[0, 0, :, pl.ds(start, bq)]
            s_t = jax.lax.dot_general(
                kb, qb, _NT, preferred_element_type=jnp.float32)
            p_t = jnp.exp(s_t - lse)                        # (bk, bq)
            if masked:
                p_t = jnp.where(
                    _causal_keep(q0 + start, k_start, bq, bk,
                                 transposed=True, window=window),
                    p_t, 0.0)
            dv = dv + jax.lax.dot_general(
                p_t.astype(dob.dtype), dob, _NN,
                preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(
                vb, dob, _NT, preferred_element_type=jnp.float32)
            ds_t = p_t * (dp_t - delta)
            dk = dk + jax.lax.dot_general(
                ds_t.astype(qb.dtype), qb, _NN,
                preferred_element_type=jnp.float32)
            return dk, dv

        def finish(carry):
            dk, dv = carry
            dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
            dv_ref[0, 0] = dv.astype(dv_ref.dtype)

        # causal: q tiles wholly before this kv tile are never
        # visited, those on its diagonal are masked, the rest plain
        # (up to the window's edge, where there is one)
        zeros = jnp.zeros(kb.shape, jnp.float32)
        _across_major_blocks(
            pl, scratch, (zeros, zeros),
            lambda carry: _walk(
                tile, carry, (k_start - q0) // bq, max(1, bk // bq),
                n_tiles, causal=causal, cross_first=True,
                whole=not scratch,
                edge=_queries_edge(k_start, q0, bq, bk, window)),
            finish)

    return kernel


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("causal", "scale", "bq", "bk", "interpret", "window"))
def flash_attention_bwd_bhsd(q, k, v, do, lse, delta, *, causal=True,
                             scale=None, bq=None, bk=None,
                             interpret=False, window=None):
    """Fused backward: (dq, dk, dv) from saved (q, k, v, lse) and the
    output-gradient rowsum delta = sum(do * o, -1, keepdims=True); lse
    and delta are (B, H, S, 1) per the forward's tiling note.

    ``bq`` / ``bk`` given are both kernels' tiles; left out, each
    kernel takes :func:`flash_tiles`'s.
    """
    from jax.experimental import pallas as pl

    b, h, s, d = q.shape
    scale = scale or (d ** -0.5)
    explicit = bq is not None or bk is not None
    window = _window(window, causal, s)
    tq = flash_tiles("dq", s, d, q.dtype.itemsize, bq, bk)
    tk = flash_tiles("dkv", s, d, q.dtype.itemsize, bq, bk)
    _count_tiles("dq", s, d, tq, explicit, window)
    _count_tiles("dkv", s, d, tk, explicit, window)

    q_tile = _tile_spec(pl, tq.bq, d)
    column = _tile_spec(pl, tq.bq, 1)
    k_block = _streamed_block(causal, tq.bq, tq.major, upto=True,
                              window=window)
    kv_major = pl.BlockSpec(
        (1, 1, tq.major, d),
        lambda bi, hi, i, j: (bi, hi, k_block(i, j), 0))
    dq = pl.pallas_call(
        _make_dq_kernel(tq, causal, scale, window),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, h, s // tq.bq, s // tq.major),
        in_specs=[q_tile, kv_major, kv_major, q_tile, column, column],
        out_specs=q_tile,
        scratch_shapes=_scratch(tq, s, (tq.bq, d)),
        compiler_params=_compiler_params(tq),
        interpret=interpret,
        name="sparkdl_flash_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv reads lse and delta as lane-dense rows: (B, H, 1, S)
    # holds the same numbers in the same order
    rows = (b, h, 1, s)
    q_block = _streamed_block(causal, tk.bk, tk.major, upto=False,
                              window=window)
    k_tile = _tile_spec(pl, tk.bk, d)
    q_major = pl.BlockSpec(
        (1, 1, tk.major, d),
        lambda bi, hi, i, j: (bi, hi, q_block(i, j), 0))
    row = pl.BlockSpec(
        (1, 1, 1, tk.major),
        lambda bi, hi, i, j: (bi, hi, 0, q_block(i, j)))
    dk, dv = pl.pallas_call(
        _make_dkv_kernel(tk, causal, scale, window),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        grid=(b, h, s // tk.bk, s // tk.major),
        in_specs=[q_major, k_tile, k_tile, q_major, row, row],
        out_specs=(k_tile, k_tile),
        scratch_shapes=_scratch(tk, s, (tk.bk, d), (tk.bk, d)),
        compiler_params=_compiler_params(tk),
        interpret=interpret,
        name="sparkdl_flash_dkv",
    )(q, k, v, do, lse.reshape(rows), delta.reshape(rows))
    return dq, dk, dv
