"""Pallas TPU flash attention (forward).

The hot op of every transformer in the model zoo. Design (per the
pallas TPU playbook):

- grid ``(batch, heads, q_blocks)``; each program holds one q tile in
  VMEM and streams K/V tiles of its (batch, head) slice through the
  MXU, maintaining the numerically stable running-softmax state
  (m, l, acc) in fp32 registers — attention scores never materialize
  in HBM, so memory is O(S·D) instead of O(S²).
- causal masking prunes the k-loop: q block i only visits k blocks
  ``<= ceil((i+1)·BQ / BK)`` (no wasted MXU work on fully-masked
  tiles); the partial diagonal tile is masked with an iota compare.
- fp32 accumulation with ``preferred_element_type`` on both matmuls;
  bf16 inputs hit the MXU natively.

The public wrapper pads S to the tile size and handles (B, S, H, D)
layout. The BACKWARD is fused too: the forward saves only the per-row
logsumexp (B, H, S); backward recomputes attention probabilities
tile-by-tile from (q, k, lse) and accumulates dq (one kernel, grid over
q tiles) and dk/dv (one kernel, grid over kv tiles) — standard
flash-attention backward, O(S·D) memory end to end, causal-pruned in
both directions.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _causal_keep(q_start, k_start, bq, bk):
    """Block-local causal visibility mask (q_pos >= k_pos), shared by
    the forward and both backward kernels so masking semantics can
    never diverge between them."""
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos >= k_pos



def _make_kernel(bq, bk, seq_len, causal, scale, with_lse=False):
    from jax.experimental import pallas as pl

    n_k_blocks = seq_len // bk

    def kernel(q_ref, k_ref, v_ref, o_ref, *maybe_lse):
        qi = pl.program_id(2)
        # Matmul INPUTS stay in the storage dtype (bf16 on TPU): the
        # MXU takes bf16 natively at full rate, while fp32 operands
        # run as multi-pass bf16 splits — casting up front would
        # throttle both matmuls. fp32 happens where it matters: the
        # accumulators (preferred_element_type) and the softmax state.
        q = q_ref[0, 0]                                      # (bq, d)
        d = q.shape[-1]

        def body(j, carry):
            m, l, acc = carry
            kb = k_ref[0, 0, pl.ds(j * bk, bk), :]
            vb = v_ref[0, 0, pl.ds(j * bk, bk), :]
            s_ij = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                         # (bq, bk)
            if causal:
                s_ij = jnp.where(
                    _causal_keep(qi * bq, j * bk, bq, bk), s_ij, NEG_INF
                )
            m_blk = jnp.max(s_ij, axis=-1)
            m_new = jnp.maximum(m, m_blk)
            p = jnp.exp(s_ij - m_new[:, None])
            p = jnp.where((m_new <= NEG_INF / 2)[:, None], 0.0, p)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            # p in [0,1] keeps full relative precision through the
            # bf16 cast; the accumulation below stays fp32
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_new = acc * alpha[:, None] + pv
            return m_new, l_new, acc_new

        m0 = jnp.full((bq,), NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        acc0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
        if causal:
            # last k block this q block can see (prunes future tiles)
            upper = jnp.minimum(
                (qi * bq + bq + bk - 1) // bk, n_k_blocks
            )
        else:
            upper = n_k_blocks
        m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
        out = acc / jnp.maximum(l, 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)
        if with_lse:
            # logsumexp per row: softmax probs are exp(s - lse) in bwd.
            # Carried as (..., bq, 1): TPU tiling requires the last two
            # block dims to be (mult of 8, mult of 128 | full dim) — a
            # rank-3 (1, 1, bq) block violates that on real hardware.
            maybe_lse[0][0, 0] = (
                m + jnp.log(jnp.maximum(l, 1e-30))
            )[:, None]

    return kernel


def flash_attention_bhsd(q, k, v, *, causal=True, scale=None, bq=128,
                         bk=128, interpret=False, return_lse=False):
    """Flash attention on (batch, heads, seq, head_dim) arrays.

    seq must be divisible by the block sizes (the public wrapper in
    :mod:`sparkdl_tpu.ops.attention` pads). With ``return_lse`` also
    returns the per-row logsumexp (B, H, S, 1) for the fused backward
    (trailing singleton: see the tiling note in the kernel).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    scale = scale or (d ** -0.5)
    bq = min(bq, s)
    bk = min(bk, s)
    if s % bq or s % bk:
        raise ValueError(f"seq {s} must be divisible by bq={bq}, bk={bk}")

    kernel = _make_kernel(bq, bk, s, causal, scale, with_lse=return_lse)
    grid = (b, h, s // bq)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, d), lambda bi, hi, i: (bi, hi, 0, 0))
    lse_spec = pl.BlockSpec(
        (1, 1, bq, 1), lambda bi, hi, i: (bi, hi, i, 0)
    )
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        out_shape = (
            out_shape,
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        )
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=(q_spec, lse_spec) if return_lse else q_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        name="sparkdl_flash_fwd",
    )(q, k, v)
    return out


def _make_dq_kernel(bq, bk, seq_len, causal, scale):
    from jax.experimental import pallas as pl

    n_k_blocks = seq_len // bk

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref):
        qi = pl.program_id(2)
        # bf16 operands into every matmul (MXU-native rate), fp32
        # accumulators — see the forward kernel's dtype note.
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0, :, 0]                           # (bq,)
        delta = delta_ref[0, 0, :, 0]                       # (bq,)

        def body(j, dq):
            kb = k_ref[0, 0, pl.ds(j * bk, bk), :]
            vb = v_ref[0, 0, pl.ds(j * bk, bk), :]
            s_ij = jax.lax.dot_general(
                q, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            p = jnp.exp(s_ij - lse[:, None])
            if causal:
                p = jnp.where(
                    _causal_keep(qi * bq, j * bk, bq, bk), p, 0.0
                )
            dp = jax.lax.dot_general(
                do, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[:, None]) * scale
            return dq + jax.lax.dot_general(
                ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        upper = (
            jnp.minimum((qi * bq + bq + bk - 1) // bk, n_k_blocks)
            if causal else n_k_blocks
        )
        dq = jax.lax.fori_loop(
            0, upper, body, jnp.zeros(q.shape, jnp.float32)
        )
        dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    return kernel


def _make_dkv_kernel(bq, bk, seq_len, causal, scale):
    from jax.experimental import pallas as pl

    n_q_blocks = seq_len // bq

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dk_ref, dv_ref):
        ki = pl.program_id(2)
        # bf16 operands into every matmul (MXU-native rate), fp32
        # accumulators — see the forward kernel's dtype note.
        kb = k_ref[0, 0]                                    # (bk, d)
        vb = v_ref[0, 0]

        def body(i, carry):
            dk, dv = carry
            qb = q_ref[0, 0, pl.ds(i * bq, bq), :]
            dob = do_ref[0, 0, pl.ds(i * bq, bq), :]
            lse = lse_ref[0, 0, pl.ds(i * bq, bq), 0]
            delta = delta_ref[0, 0, pl.ds(i * bq, bq), 0]
            s_ij = jax.lax.dot_general(
                qb, kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale                                       # (bq, bk)
            p = jnp.exp(s_ij - lse[:, None])
            if causal:
                p = jnp.where(
                    _causal_keep(i * bq, ki * bk, bq, bk), p, 0.0
                )
            dv = dv + jax.lax.dot_general(
                p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                dob, vb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta[:, None]) * scale
            return dk + jax.lax.dot_general(
                ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ), dv

        # causal: only q blocks at or after this kv block contribute
        lower = (ki * bk) // bq if causal else 0
        dk0 = jnp.zeros(kb.shape, jnp.float32)
        dv0 = jnp.zeros(vb.shape, jnp.float32)
        dk, dv = jax.lax.fori_loop(lower, n_q_blocks, body, (dk0, dv0))
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv.astype(dv_ref.dtype)

    return kernel


def flash_attention_bwd_bhsd(q, k, v, do, lse, delta, *, causal=True,
                             scale=None, bq=128, bk=128, interpret=False):
    """Fused backward: (dq, dk, dv) from saved (q, k, v, lse) and the
    output-gradient rowsum delta = sum(do * o, -1, keepdims=True); lse
    and delta are (B, H, S, 1) per the forward's tiling note."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d = q.shape
    scale = scale or (d ** -0.5)
    bq = min(bq, s)
    bk = min(bk, s)
    if s % bq or s % bk:
        raise ValueError(f"seq {s} must be divisible by bq={bq}, bk={bk}")

    q_tile = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i: (bi, hi, i, 0))
    k_tile = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, i: (bi, hi, i, 0))
    full_s = pl.BlockSpec((1, 1, s, d), lambda bi, hi, i: (bi, hi, 0, 0))
    vec_q = pl.BlockSpec(
        (1, 1, bq, 1), lambda bi, hi, i: (bi, hi, i, 0)
    )
    vec_full = pl.BlockSpec(
        (1, 1, s, 1), lambda bi, hi, i: (bi, hi, 0, 0)
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"),
    )

    dq = pl.pallas_call(
        _make_dq_kernel(bq, bk, s, causal, scale),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b, h, s // bq),
        in_specs=[q_tile, full_s, full_s, q_tile, vec_q, vec_q],
        out_specs=q_tile,
        compiler_params=params,
        interpret=interpret,
        name="sparkdl_flash_dq",
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        _make_dkv_kernel(bq, bk, s, causal, scale),
        out_shape=(
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ),
        grid=(b, h, s // bk),
        in_specs=[full_s, k_tile, k_tile, full_s, vec_full, vec_full],
        out_specs=(k_tile, k_tile),
        compiler_params=params,
        interpret=interpret,
        name="sparkdl_flash_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv
