"""Pallas TPU weight-only int8/int4 matmul with fused dequant.

Serving-side kernels (pallas guide §Quantization): weights live in HBM
as int8 (or nibble-packed int4) with fp32 scales — half/quarter the
bytes of bf16/fp32, which matters because decode-time matmuls are
HBM-bandwidth bound. The kernels are K-blocked: each (i, j) output
tile owns an fp32 VMEM accumulator and streams quantized weight tiles
through the MXU, dequantizing on the fly in the inner loop. Edge tiles
of non-divisible M/N/K shapes are masked in-kernel (no host-side
padding copies).

Dispatch is governed by the ``SPARKDL_TPU_KERNEL_QUANT_MATMUL`` knob
(``auto`` | ``off`` | ``force_interpret``): ``auto`` runs the kernel
on TPU and the XLA dequant lowering elsewhere, ``off`` pins the XLA
lowering everywhere, and ``force_interpret`` emulates the kernel on
any backend (the CPU equivalence oracle). Inputs the kernel cannot
serve are an error on a TPU — a path that quietly gave way to XLA
there would be measured under the kernel's name — and elsewhere
degrade to the XLA lowering loudly (RuntimeWarning), never to a wrong
answer.
"""

import functools
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

# int4 group size (rows per scale); defined up top because
# quantize_params defaults to it
INT4_GROUP = 64

KERNEL_MODE_ENV = "SPARKDL_TPU_KERNEL_QUANT_MATMUL"
KERNEL_MODES = ("auto", "off", "force_interpret")

# Read ONCE at import: quantized_matmul runs under jit inside serving
# programs and env vars are not part of the jit cache key — a
# mid-process flip must never silently re-route already-traced
# programs (same rationale as ops.attention's flash block defaults).
# Per-call overrides go through the ``mode=`` argument, which callers
# thread from LlamaConfig.quant_kernel (part of the program cache key).
_DEFAULT_MODE = os.environ.get(KERNEL_MODE_ENV, "auto")


def _kernel_plan(mode):
    """Resolve a kernel mode to ``(use_kernel, interpret)``.

    ``mode`` "" falls back to the import-time knob default."""
    from sparkdl_tpu.ops._dispatch import use_pallas

    mode = mode or _DEFAULT_MODE
    if mode not in KERNEL_MODES:
        raise ValueError(
            f"unknown quant-matmul kernel mode {mode!r}; expected one "
            f"of {KERNEL_MODES} (knob {KERNEL_MODE_ENV})")
    if mode == "off":
        return False, False
    if mode == "force_interpret":
        return True, True
    return use_pallas(), False


def _unsupported(reason):
    """The kernel was asked for and cannot serve this input: an error
    on a TPU, a loud fallback to the XLA lowering elsewhere."""
    from sparkdl_tpu.ops._dispatch import use_pallas

    if use_pallas():
        raise ValueError(
            f"quant-matmul kernel unsupported ({reason}); pass "
            "mode='off' to ask for the XLA dequant lowering")
    warnings.warn(
        f"quant-matmul kernel unsupported ({reason}); degrading to the "
        "XLA dequant lowering", RuntimeWarning, stacklevel=3)


def quantize_int8(w):
    """Per-output-channel symmetric int8 quantization of a (K, N)
    weight matrix → (w_q int8 (K, N), scales fp32 (N,))."""
    w = np.asarray(w, np.float32)
    scales = np.abs(w).max(axis=0) / 127.0
    scales = np.where(scales == 0.0, 1.0, scales).astype(np.float32)
    w_q = np.clip(np.round(w / scales[None, :]), -127, 127).astype(np.int8)
    return w_q, scales


def _qmm_kernel(nk, k, bk, x_ref, wq_ref, scale_ref, o_ref, acc_ref):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:].astype(jnp.float32)
    if k % bk:
        # ragged final K tile: columns past K are block padding and may
        # hold anything — zero them out of the contraction (the int8
        # weight tile is finite garbage there, so masking x suffices)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(kpos < k, x, 0.0)
    w = wq_ref[:].astype(jnp.float32)
    acc_ref[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _finalize():
        # per-column scales factor out of the K-sum, so one multiply at
        # the end is exact — the int8→fp32 dequant itself happens in
        # the inner loop feeding the MXU
        o_ref[:] = (acc_ref[:] * scale_ref[:]).astype(o_ref.dtype)


def quantized_matmul_pallas(x, w_q, scales, *, block_m=128, block_n=128,
                            block_k=512, interpret=False):
    """x (M, K) @ dequant(w_q (K, N)) with per-column scales (N,).

    K-blocked with an fp32 VMEM accumulator; non-divisible M/N/K are
    served by masked edge tiles, not host padding."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from sparkdl_tpu.ops._dispatch import block_for

    m, k = x.shape
    _, n = w_q.shape
    bm = block_for(m, tile=block_m)
    bn = block_for(n, tile=block_n, floor=128)
    bk = min(block_k, k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_qmm_kernel, grid[2], k, bk),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk, bn), lambda i, j, ki: (ki, j)),
            # scales ride as a (1, N) row: a 1-D f32 operand gets XLA's
            # T(1024) layout on TPU, which Mosaic refuses for a 128 block
            pl.BlockSpec((1, bn), lambda i, j, ki: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # K innermost and sequential: the accumulator carries
            # across k steps of one (i, j) tile
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sparkdl_qmm_int8",
    )(x, w_q, scales.reshape(1, n))


def quantized_matmul(x, w_q, scales, *, interpret=None, mode=""):
    """Dispatch: pallas kernel per the ``mode`` plan (see module
    docstring), XLA dequant-matmul otherwise.

    ``interpret`` is the legacy per-call override (True → interpreted
    kernel, False → compiled kernel) and wins over ``mode``."""
    if scales.shape != (w_q.shape[1],):
        # caller bug, not a kernel limitation: the XLA lowering would
        # broadcast a mis-shaped scale vector into a wrong-SHAPED
        # product, so there is no correct lowering to degrade to
        raise ValueError(
            f"scales shape {scales.shape} does not match N={w_q.shape[1]}")
    if interpret is not None:
        use_kernel, interp = True, bool(interpret)
    else:
        use_kernel, interp = _kernel_plan(mode)
    if use_kernel and w_q.dtype != jnp.int8:
        _unsupported(f"w_q dtype {w_q.dtype} is not int8")
        use_kernel = False
    if not use_kernel:
        w = w_q.astype(jnp.float32) * scales[None, :]
        return (x.astype(jnp.float32) @ w).astype(x.dtype)
    return quantized_matmul_pallas(x, w_q, scales, interpret=interp)


# Dense layers quantized by default: every 2-D projection of the
# decoder family; embeddings stay dense (a lookup reads one row).
DEFAULT_QUANT_TARGETS = ("gate_proj", "up_proj", "down_proj",
                         "q_proj", "k_proj", "v_proj",
                         "o_proj", "lm_head")


def quantize_params(params, targets=DEFAULT_QUANT_TARGETS, bits=8,
                    group=INT4_GROUP):
    """Quantize matching kernel leaves of a flax param tree →
    (new_params, bytes saved). ``bits=8``: per-column int8
    ('kernel_q' + 'kernel_scale'). ``bits=4``: group-wise nibble-packed
    int4 ('kernel_q4' + 'kernel_scale4')."""
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    saved = [0]

    def walk(node, name=""):
        if isinstance(node, dict):
            if ("kernel" in node and any(t in name for t in targets)
                    and getattr(node["kernel"], "ndim", 0) == 2):
                orig = node["kernel"]
                if bits == 8:
                    w_q, s = quantize_int8(np.asarray(orig, np.float32))
                    names = ("kernel_q", "kernel_scale")
                else:
                    w_q, s = quantize_int4(
                        np.asarray(orig, np.float32), group=group)
                    names = ("kernel_q4", "kernel_scale4")
                # savings accounted against the ORIGINAL dtype (bf16
                # kernels are 2 bytes/elt, not 4)
                saved[0] += (
                    np.asarray(orig).nbytes - w_q.nbytes - s.nbytes
                )
                out = dict(node)
                out[names[0]] = w_q
                out[names[1]] = s
                del out["kernel"]
                return out
            return {k: walk(v, k) for k, v in node.items()}
        return node

    return walk(params), saved[0]


def dequantize_params(qparams, dtype=jnp.bfloat16):
    """Reconstruct an apply-compatible param tree from
    :func:`quantize_params` output: every (kernel_q, kernel_scale) pair
    becomes a dense ``kernel`` again. Use this to run a standard
    ``model.apply`` off a quantized checkpoint; serving stacks that
    call :func:`quantized_matmul` directly can keep the int8 leaves."""

    def walk(node):
        if isinstance(node, dict):
            if "kernel_q" in node:
                out = {k: v for k, v in node.items()
                       if k not in ("kernel_q", "kernel_scale")}
                out["kernel"] = (
                    jnp.asarray(node["kernel_q"], jnp.float32)
                    * jnp.asarray(node["kernel_scale"])[None, :]
                ).astype(dtype)
                return out
            if "kernel_q4" in node:
                out = {k: v for k, v in node.items()
                       if k not in ("kernel_q4", "kernel_scale4")}
                scales = jnp.asarray(node["kernel_scale4"])
                k_full = 2 * node["kernel_q4"].shape[0]
                group = k_full // scales.shape[0]
                out["kernel"] = _dequant_int4(
                    jnp.asarray(node["kernel_q4"]), scales, group
                ).astype(dtype)
                return out
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(qparams)


# ---------------------------------------------------------------------------
# int4 weight-only: two nibbles per int8 byte along K, GROUP-wise
# scales (finer than int8's per-column — int4's 15 levels need them).
# Quarter the weight bytes of bf16; decode is HBM-bound, so bytes are
# step time.
# ---------------------------------------------------------------------------


def quantize_int4(w, group=INT4_GROUP):
    """Group-wise symmetric int4 quantization of (K, N) →
    (packed int8 (K//2, N), scales fp32 (K//group, N)). Row 2i rides
    the LOW nibble of packed row i, row 2i+1 the HIGH nibble."""
    w = np.asarray(w, np.float32)
    k, n = w.shape
    if k % max(group, 2):
        raise ValueError(f"K={k} must be divisible by group={group} (and 2)")
    g = w.reshape(k // group, group, n)
    scales = np.abs(g).max(axis=1) / 7.0              # (K//group, N)
    scales = np.where(scales == 0.0, 1.0, scales).astype(np.float32)
    w_q = np.clip(np.round(g / scales[:, None, :]), -7, 7)
    w_q = w_q.reshape(k, n).astype(np.int8)
    low = w_q[0::2].astype(np.uint8) & 0x0F
    high = (w_q[1::2].astype(np.uint8) & 0x0F) << 4
    packed = (low | high).view(np.int8)               # (K//2, N)
    return packed, scales


def unpack_int4(packed):
    """(K//2, N) packed int8 → (K, N) int8 in [-7, 7] (sign-extended
    nibbles; jnp ops only, shared by the kernel and the XLA path)."""
    p = packed.astype(jnp.int8)
    low = jnp.right_shift(jnp.left_shift(p, 4), 4)    # sign-extend low
    high = jnp.right_shift(p, 4)                      # arithmetic
    kh, n = p.shape
    return jnp.stack([low, high], axis=1).reshape(2 * kh, n)


def _dequant_int4(packed, scales, group):
    w = unpack_int4(packed).astype(jnp.float32)
    return w * jnp.repeat(scales, group, axis=0)


def _q4mm_kernel(group, nk, kh, bkh, xlo_ref, xhi_ref, wq_ref, scale_ref,
                 o_ref, acc_ref):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Nibbles unpack in 32-bit arithmetic (Mosaic legalises no shift on
    # 8-bit vectors) and stay in PACKED row order: packed row i holds
    # weight rows 2i (low) and 2i+1 (high), both of scale group
    # i // (group // 2), so the even/odd activation columns the wrapper
    # split off meet them as two dots and no row interleave is needed.
    p = wq_ref[:].astype(jnp.int32)
    low = ((p & 0xF) ^ 8) - 8                         # sign-extend
    high = p >> 4                                     # arithmetic
    s = scale_ref[:]
    reps = group // 2
    s = jnp.broadcast_to(
        s[:, None, :], (s.shape[0], reps, s.shape[1])
    ).reshape(s.shape[0] * reps, s.shape[1])          # (bk // 2, bn)
    w_lo = low.astype(jnp.float32) * s
    w_hi = high.astype(jnp.float32) * s
    x_lo = xlo_ref[:].astype(jnp.float32)
    x_hi = xhi_ref[:].astype(jnp.float32)
    if kh % bkh:
        # ragged final K tile: block padding past K may hold anything
        # (the padded fp32 scale rows in particular) — zero BOTH
        # operands so no garbage (or NaN) reaches the accumulator
        kpos = ki * bkh + jax.lax.broadcasted_iota(jnp.int32, x_lo.shape, 1)
        x_lo = jnp.where(kpos < kh, x_lo, 0.0)
        x_hi = jnp.where(kpos < kh, x_hi, 0.0)
        wpos = ki * bkh + jax.lax.broadcasted_iota(jnp.int32, w_lo.shape, 0)
        w_lo = jnp.where(wpos < kh, w_lo, 0.0)
        w_hi = jnp.where(wpos < kh, w_hi, 0.0)
    dims = (((1,), (0,)), ((), ()))
    acc_ref[:] += (
        jax.lax.dot_general(x_lo, w_lo, dims,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(x_hi, w_hi, dims,
                              preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def quantized_matmul_int4_pallas(x, packed, scales, *, group=INT4_GROUP,
                                 block_m=128, block_n=128, block_k=None,
                                 interpret=False):
    """x (M, K) @ dequant(packed (K//2, N)) with (K//group, N) scales.

    K-blocked like the int8 kernel; the K tile is a whole number of
    scale groups, and by default eight of them at least, so that the
    scale block meets the (8, 128) tiling of the TPU. ``group`` must be
    even: a packed row may not straddle two scale groups."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from sparkdl_tpu.ops._dispatch import block_for

    m, k = x.shape
    kh, n = packed.shape
    if group % 2:
        raise ValueError(
            f"the int4 kernel needs an even scale group, got {group}")
    assert k == 2 * kh, (x.shape, packed.shape)
    assert k == group * scales.shape[0], (k, group, scales.shape)
    bm = block_for(m, tile=block_m)
    bn = block_for(n, tile=block_n, floor=128)
    block_k = block_k or max(512, 8 * group)
    bk = max(group, min(block_k, k) // group * group)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_q4mm_kernel, group, grid[2], kh, bk // 2),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bm, bk // 2), lambda i, j, ki: (i, ki)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, ki: (ki, j)),
            pl.BlockSpec((bk // group, bn), lambda i, j, ki: (ki, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, ki: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sparkdl_qmm_int4",
    )(x[:, 0::2], x[:, 1::2], packed, scales)


def quantized_matmul_int4(x, packed, scales, *, group=INT4_GROUP,
                          interpret=None, mode=""):
    """Dispatch like :func:`quantized_matmul`, plus int4-specific
    support checks: a ``group`` that does not cover K with the given
    scale rows, or an odd one, degrades loudly to the XLA lowering
    under the group the shapes imply (never a wrong answer; an error
    on a TPU), and raises when no consistent group exists."""
    k = x.shape[1]
    s_rows = scales.shape[0]
    if k != 2 * packed.shape[0]:
        raise ValueError(
            f"packed int4 weight has {packed.shape[0]} rows; K={k} "
            "activations need K//2")
    if interpret is not None:
        use_kernel, interp = True, bool(interpret)
    else:
        use_kernel, interp = _kernel_plan(mode)
    if group <= 0 or group * s_rows != k:
        if s_rows == 0 or k % s_rows:
            raise ValueError(
                f"int4 scales with {s_rows} rows cannot cover K={k} "
                f"under any group (requested group={group})")
        inferred = k // s_rows
        _unsupported(
            f"group={group} does not cover K={k} with {s_rows} scale "
            f"rows; using inferred group={inferred}")
        group, use_kernel = inferred, False
    if use_kernel and packed.dtype != jnp.int8:
        _unsupported(f"packed dtype {packed.dtype} is not int8")
        use_kernel = False
    if use_kernel and group % 2:
        _unsupported(f"odd scale group {group}")
        use_kernel = False
    if not use_kernel:
        w = _dequant_int4(packed, scales, group)
        return (x.astype(jnp.float32) @ w).astype(x.dtype)
    return quantized_matmul_int4_pallas(
        x, packed, scales, group=group, interpret=interp)
