"""Ring attention: exact attention over sequences sharded across the
``seq`` mesh axis.

Long-context training shards the sequence dimension across chips; each
chip holds a Q/K/V block and K/V blocks rotate around the ring via
``lax.ppermute`` (neighbor exchange → pure ICI traffic, no all-to-all),
while softmax statistics accumulate in the numerically stable
flash-attention form (running max + rescaled partial sums). After
``seq`` steps every query block has attended to every key block —
bit-exact full attention with O(S/N) activation memory per chip.

**Communication/compute overlap** (the default, ``overlap=True``): the
ring is software-pipelined so the ``ppermute`` moving the NEXT K/V
block is issued *before* the CURRENT block is attended — the hop's
only data dependence is the block that already arrived, so XLA's async
collective scheduler (``collective-permute-start``/``-done`` plus the
while-loop collective pipeliner) can run the wire transfer concurrently
with the block attention instead of serializing attend → hop → attend.
Same blocks, same merge order, same hop count as the serialized
schedule — outputs are bit-exact against ``overlap=False`` (pinned by
tests) and against :func:`attention_reference`.

The reference has no sequence parallelism at all (SURVEY.md §5.7); this
is the capability the build brief requires beyond parity. Use under
``shard_map`` with Q/K/V sharded on the sequence dimension.
"""

import functools

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def _block_attend(q, k, v, mask, scale):
    """One (q-block × kv-block) attention piece with its own softmax
    stats. Shapes: q (B,Sq,H,D), k/v (B,Sk,H,D), mask (Sq,Sk) or None.
    Returns (o, m, l): unnormalized output, row max, row sum.

    Matmuls run in the INPUT dtype with fp32 accumulation
    (``preferred_element_type``): upcasting bf16 operands to fp32
    first would push the MXU to its multi-pass fp32 rate (the same
    throttle the round-4 flash-kernel fix removed), while softmax
    statistics and the accumulators stay fp32 for stability — the
    standard flash-attention numerics."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # (B,H,Sq)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(m[..., None] <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)                      # (B,H,Sq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, m, l


def _merge_stats(acc_o, acc_m, acc_l, o, m, l):
    """Fold one block's (o, m, l) into the running flash accumulators —
    the ONE merge both ring schedules share, so the overlapped lowering
    stays bit-exact against the serialized one."""
    new_m = jnp.maximum(acc_m, m)
    a = jnp.exp(acc_m - new_m)
    bfac = jnp.exp(m - new_m)
    acc_o = (acc_o * a[..., None].transpose(0, 2, 1, 3)
             + o * bfac[..., None].transpose(0, 2, 1, 3))
    acc_l = acc_l * a + l * bfac
    return acc_o, new_m, acc_l


def resolve_overlap(overlap):
    """The hop-schedule default: an explicit ``overlap`` wins; ``None``
    resolves the ``SPARKDL_TPU_OVERLAP`` env knob (registered in
    :mod:`sparkdl_tpu.utils.knobs`; on when unset) — the seam an
    autotuned profile flips per device kind without touching call
    sites. Read at trace time, like every other schedule choice."""
    if overlap is not None:
        return bool(overlap)
    from sparkdl_tpu.utils.knobs import read_bool

    return read_bool("SPARKDL_TPU_OVERLAP")


def ring_self_attention(q, k, v, *, axis_name, causal=True, scale=None,
                        overlap=None):
    """Exact (flash-accumulated) self-attention with K/V ring rotation.

    Args: q, k, v of shape (batch, seq_local, heads, head_dim) — the
    local sequence shard; must be called inside ``shard_map`` with the
    sequence dimension sharded over ``axis_name``.

    ``overlap=True`` (default; ``None`` resolves the
    ``SPARKDL_TPU_OVERLAP`` knob) issues each hop's ``ppermute`` before
    attending the block that already arrived (double-buffered carry:
    the resident block is consumed while its successor is on the
    wire), so the transfer hides under the block attention.
    ``overlap=False`` keeps the serialized attend → hop schedule — the
    equivalence oracle and the analysis bad-corpus generator.
    """
    overlap = resolve_overlap(overlap)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale or (d ** -0.5)

    q_pos = idx * s_local + jnp.arange(s_local)

    def make_mask(src):
        if not causal:
            return None
        k_pos = src * s_local + jnp.arange(s_local)
        return q_pos[:, None] >= k_pos[None, :]

    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend_merge(acc, k_blk, v_blk, src):
        mask = make_mask(src) if causal else None
        o, m, l = _block_attend(q, k_blk, v_blk, mask, scale)
        return _merge_stats(*acc, o, m, l)

    acc = (
        jnp.zeros((b, s_local, h, d), jnp.float32),
        jnp.full((b, h, s_local), NEG_INF, jnp.float32),
        jnp.zeros((b, h, s_local), jnp.float32),
    )

    if not overlap:
        def step(carry, _):
            k_blk, v_blk, src, acc_o, acc_m, acc_l = carry
            acc_o, acc_m, acc_l = attend_merge(
                (acc_o, acc_m, acc_l), k_blk, v_blk, src)
            # rotate kv to the next rank (neighbor exchange on the ring)
            k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
            src_nxt = (src - 1) % n
            return (k_nxt, v_nxt, src_nxt, acc_o, acc_m, acc_l), None

        carry = (k, v, idx) + acc
        (_, _, _, acc_o, _, acc_l), _ = jax.lax.scan(
            step, carry, None, length=n
        )
    else:
        # Hop 0 is the resident block; hop 1's permute is issued BEFORE
        # attending it, so the first transfer is already in flight while
        # the diagonal block computes.
        if n == 1:
            acc_o, _, acc_l = attend_merge(acc, k, v, idx)
        else:
            k_cur = jax.lax.ppermute(k, axis_name, perm)
            v_cur = jax.lax.ppermute(v, axis_name, perm)
            acc = attend_merge(acc, k, v, idx)

            def step(carry, _):
                k_cur, v_cur, src, acc_o, acc_m, acc_l = carry
                # issue the NEXT hop first: its only dependence is the
                # block that already arrived, so the wire transfer and
                # the block attention below can run concurrently
                k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
                v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
                acc_o, acc_m, acc_l = attend_merge(
                    (acc_o, acc_m, acc_l), k_cur, v_cur, src)
                return (k_nxt, v_nxt, (src - 1) % n,
                        acc_o, acc_m, acc_l), None

            carry = (k_cur, v_cur, (idx - 1) % n) + acc
            (_, _, _, acc_o, _, acc_l), _ = jax.lax.scan(
                step, carry, None, length=n - 1
            )
    denom = jnp.maximum(acc_l, 1e-30)[..., None].transpose(0, 2, 1, 3)
    return (acc_o / denom).astype(q.dtype)


def attention_reference(q, k, v, *, causal=True, scale=None, window=None):
    """Dense single-device attention (test oracle / the headline
    TRAINING path — ``LlamaConfig.attention="reference"``). With
    `window` (causal only) query i sees the keys j with
    ``0 <= i - j < window``.

    Same MXU discipline as :func:`_block_attend`: scores and the PV
    product run in the input dtype with fp32 accumulation; only the
    softmax itself is fp32. For fp32 inputs (every oracle test) this
    is bit-identical to the old always-upcast version; for the bf16
    training path it keeps the two big einsums at full MXU rate."""
    d = q.shape[-1]
    scale = scale or (d ** -0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if window is not None and not causal:
        raise ValueError("a window is causal")
    if causal:
        sq, sk = s.shape[-2:]
        back = jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :]
        mask = back >= 0
        if window is not None:
            mask = mask & (back < window)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    # back to the activation dtype: a silently-fp32 output would
    # upcast the caller's o_proj matmul (the throttle this fix removes)
    return o.astype(v.dtype)


# ---------------------------------------------------------------------------
# Ring-flash: pallas flash kernels INSIDE the ring (long-context scale).
#
# The dense ring above materializes a (B, H, S_local, S_local) fp32
# score matrix every ring step — at the sequence lengths sequence
# parallelism exists for (S_local in the thousands), that buffer is the
# memory wall.  Here each ring step runs the fused pallas forward on
# the resident K/V block (O(S_local · D) memory), and normalized
# partials merge in logsumexp form.  The backward is a SECOND ring
# pass (custom_vjp): with the forward's final lse and delta = Σ do·o,
# the flash backward restricted to one K/V block is exactly the
# block's contribution, so dq accumulates locally while dk/dv
# accumulators rotate WITH their blocks and arrive home after n hops
# (blockwise-parallel ring attention; same decomposition the in-tree
# dq/dkv kernels already implement across tiles within a block).
#
# Both rings are software-pipelined like the dense one (overlap=True):
# the K/V hop — and, in the backward, the dk/dv accumulator hop, whose
# incoming value is only needed AFTER the block backward — is issued
# before the resident block's kernel runs, so the ICI transfer hides
# under the pallas compute.
#
# Visibility schedule (causal): at hop t the resident block came from
# rank src = (idx - t) mod n — src == idx is the causal diagonal
# (t = 0, unrolled before the scan), src < idx is fully visible,
# src > idx is fully masked and skipped without touching the MXU.
# ---------------------------------------------------------------------------


def _lse_merge(acc_o, acc_lse, o, lse):
    """Merge one normalized block partial in logsumexp form — shared
    by both flash-ring schedules (bit-exactness contract)."""
    new_lse = jnp.logaddexp(acc_lse, lse)
    acc_o = (acc_o * jnp.exp(acc_lse - new_lse)
             + o * jnp.exp(lse - new_lse))
    return acc_o, new_lse


def _ring_flash_fwd_pass(qt, k0, v0, axis_name, causal, scale, bq, bk,
                         interpret, overlap=True):
    """Ring of flash-forward blocks. qt/k0/v0 are (B,H,S,D) local
    shards; returns (o_norm f32, lse f32 (B,H,S,1))."""
    from sparkdl_tpu.ops.pallas.flash_attention import (
        flash_attention_bhsd,
    )

    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    b, h, s, d = qt.shape

    def attend(k_blk, v_blk, diag):
        o, lse = flash_attention_bhsd(
            qt, k_blk, v_blk, causal=diag and causal, scale=scale,
            bq=bq, bk=bk, interpret=interpret, return_lse=True,
        )
        return o.astype(jnp.float32), lse

    def masked_attend(k_blk, v_blk, src):
        if causal:
            return jax.lax.cond(
                src < idx,
                lambda: attend(k_blk, v_blk, diag=False),
                lambda: (jnp.zeros((b, h, s, d), jnp.float32),
                         jnp.full((b, h, s, 1), NEG_INF, jnp.float32)),
            )
        return attend(k_blk, v_blk, diag=False)

    if not overlap:
        # hop 0: the resident (own) block — the causal diagonal
        acc_o, acc_lse = attend(k0, v0, diag=True)

        def step(carry, _):
            k_blk, v_blk, src, acc_o, acc_lse = carry
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            src = (src - 1) % n
            o, lse = masked_attend(k_blk, v_blk, src)
            acc_o, acc_lse = _lse_merge(acc_o, acc_lse, o, lse)
            return (k_blk, v_blk, src, acc_o, acc_lse), None

        (_, _, _, acc_o, acc_lse), _ = jax.lax.scan(
            step, (k0, v0, idx, acc_o, acc_lse), None, length=n - 1
        )
        return acc_o, acc_lse

    if n == 1:
        return attend(k0, v0, diag=True)
    # hop 1's permute is issued BEFORE the diagonal kernel runs
    k_cur = jax.lax.ppermute(k0, axis_name, perm)
    v_cur = jax.lax.ppermute(v0, axis_name, perm)
    acc_o, acc_lse = attend(k0, v0, diag=True)

    def step(carry, _):
        k_cur, v_cur, src, acc_o, acc_lse = carry
        # next hop rides the wire while the resident block computes
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        o, lse = masked_attend(k_cur, v_cur, src)
        acc_o, acc_lse = _lse_merge(acc_o, acc_lse, o, lse)
        return (k_nxt, v_nxt, (src - 1) % n, acc_o, acc_lse), None

    (k_cur, v_cur, src, acc_o, acc_lse), _ = jax.lax.scan(
        step, (k_cur, v_cur, (idx - 1) % n, acc_o, acc_lse), None,
        length=n - 2,
    )
    # epilogue: the final block needs no further hop — attending it
    # outside the scan keeps the hop count identical to the serialized
    # schedule (n-1 permutes per tensor)
    o, lse = masked_attend(k_cur, v_cur, src)
    acc_o, acc_lse = _lse_merge(acc_o, acc_lse, o, lse)
    return acc_o, acc_lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, axis_name, causal, scale, bq, bk, interpret,
                overlap):
    out, _ = _ring_flash_core(q, k, v, axis_name, causal, scale, bq,
                              bk, interpret, overlap)
    return out


def _ring_flash_core(q, k, v, axis_name, causal, scale, bq, bk,
                     interpret, overlap):
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    acc_o, acc_lse = _ring_flash_fwd_pass(
        qt, kt, vt, axis_name, causal, scale, bq, bk, interpret,
        overlap,
    )
    out = acc_o.astype(q.dtype).transpose(0, 2, 1, 3)
    return out, acc_lse


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, bq, bk,
                    interpret, overlap):
    out, lse = _ring_flash_core(q, k, v, axis_name, causal, scale, bq,
                                bk, interpret, overlap)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, bq, bk, interpret,
                    overlap, res, do):
    from sparkdl_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd_bhsd,
    )

    q, k, v, out, lse = res
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.astype(jnp.float32).transpose(0, 2, 1, 3)
    ot = out.astype(jnp.float32).transpose(0, 2, 1, 3)
    delta = jnp.sum(dot * ot, axis=-1, keepdims=True)  # (B,H,S,1)
    dot = dot.astype(qt.dtype)

    def block_bwd(k_blk, v_blk, diag):
        return flash_attention_bwd_bhsd(
            qt, k_blk, v_blk, dot, lse, delta,
            causal=diag and causal, scale=scale, bq=bq, bk=bk,
            interpret=interpret,
        )

    zeros_kv = jnp.zeros(kt.shape, jnp.float32)

    def masked_block_bwd(k_blk, v_blk, src):
        def live():
            dq_c, dk_c, dv_c = block_bwd(k_blk, v_blk, diag=False)
            return (dq_c.astype(jnp.float32),
                    dk_c.astype(jnp.float32),
                    dv_c.astype(jnp.float32))

        if causal:
            return jax.lax.cond(
                src < idx,
                live,
                lambda: (jnp.zeros(qt.shape, jnp.float32), zeros_kv,
                         zeros_kv),
            )
        return live()

    def finish(dq_acc, dk_acc, dv_acc):
        dq = dq_acc.astype(q.dtype).transpose(0, 2, 1, 3)
        dk = dk_acc.astype(k.dtype).transpose(0, 2, 1, 3)
        dv = dv_acc.astype(v.dtype).transpose(0, 2, 1, 3)
        return dq, dk, dv

    if not overlap:
        # hop 0: diagonal block (own k/v)
        dq0, dk0, dv0 = block_bwd(kt, vt, diag=True)
        dq_acc = dq0.astype(jnp.float32)

        def step(carry, _):
            k_blk, v_blk, dk_acc, dv_acc, src, dq_acc = carry
            # rotate the block AND its gradient accumulator together:
            # after the remaining n-1 hops both are back on the
            # block's home rank
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
            src = (src - 1) % n
            dq_c, dk_c, dv_c = masked_block_bwd(k_blk, v_blk, src)
            return (k_blk, v_blk, dk_acc + dk_c, dv_acc + dv_c, src,
                    dq_acc + dq_c), None

        carry = (kt, vt, dk0.astype(jnp.float32),
                 dv0.astype(jnp.float32), idx, dq_acc)
        (k_blk, v_blk, dk_acc, dv_acc, _, dq_acc), _ = jax.lax.scan(
            step, carry, None, length=n - 1
        )
        # one more hop brings each accumulator from the rank that
        # computed the LAST contribution back to the block's home rank
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
        return finish(dq_acc, dk_acc, dv_acc)

    # overlapped second ring: K/V hop issued before the diagonal
    # kernel; in the body, the incoming accumulator is only needed
    # AFTER the block backward, so its permute hides under the kernel
    # exactly like the K/V one.
    dq_hop0, dk0, dv0 = block_bwd(kt, vt, diag=True)
    if n == 1:
        return finish(dq_hop0.astype(jnp.float32),
                      dk0.astype(jnp.float32),
                      dv0.astype(jnp.float32))
    k_cur = jax.lax.ppermute(kt, axis_name, perm)
    v_cur = jax.lax.ppermute(vt, axis_name, perm)
    dq_acc = dq_hop0.astype(jnp.float32)

    def step(carry, _):
        k_cur, v_cur, dk_acc, dv_acc, src, dq_acc = carry
        # all four permutes are independent of this hop's block
        # backward — K/V for the NEXT block, plus the accumulator for
        # the CURRENT block arriving from the previous rank
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_in = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_in = jax.lax.ppermute(dv_acc, axis_name, perm)
        dq_c, dk_c, dv_c = masked_block_bwd(k_cur, v_cur, src)
        return (k_nxt, v_nxt, dk_in + dk_c, dv_in + dv_c,
                (src - 1) % n, dq_acc + dq_c), None

    carry = (k_cur, v_cur, dk0.astype(jnp.float32),
             dv0.astype(jnp.float32), (idx - 1) % n, dq_acc)
    (k_cur, v_cur, dk_acc, dv_acc, src, dq_acc), _ = jax.lax.scan(
        step, carry, None, length=n - 2
    )
    # epilogue: the final block's contribution, then the homing hop
    dk_in = jax.lax.ppermute(dk_acc, axis_name, perm)
    dv_in = jax.lax.ppermute(dv_acc, axis_name, perm)
    dq_c, dk_c, dv_c = masked_block_bwd(k_cur, v_cur, src)
    dk_acc = jax.lax.ppermute(dk_in + dk_c, axis_name, perm)
    dv_acc = jax.lax.ppermute(dv_in + dv_c, axis_name, perm)
    return finish(dq_acc + dq_c, dk_acc, dv_acc)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, *, axis_name, causal=True, scale=None,
                         bq=128, bk=128, interpret=False, overlap=None):
    """Ring attention whose per-block compute is the fused pallas flash
    kernel — O(S_local · D) memory per hop instead of the dense ring's
    O(S_local²) score matrix, with a fused two-ring backward.  Same
    contract as :func:`ring_self_attention`: (batch, seq_local, heads,
    head_dim) shards inside ``shard_map`` over ``axis_name``;
    ``overlap`` selects the software-pipelined (default; ``None``
    resolves ``SPARKDL_TPU_OVERLAP``) vs serialized hop schedule in
    BOTH rings."""
    d = q.shape[-1]
    scale = scale or (d ** -0.5)
    return _ring_flash(q, k, v, axis_name, causal, scale, bq, bk,
                       interpret, resolve_overlap(overlap))


def make_ring_attention(mesh, *, causal=True, impl=None,
                        interpret=False, overlap=None):
    """Bind ring attention to a mesh: returns f(q, k, v) taking GLOBAL
    (b, s, h, d) arrays sharded (data, seq, None, None).

    ``impl``: "dense" (XLA block attend — any backend, the test
    oracle's numerics), "flash" (pallas blocks — the long-context
    TPU path; ``interpret=True`` runs the kernels interpreted for
    tests off-TPU), or None = flash on TPU, dense elsewhere.
    ``overlap``: software-pipelined hop schedule (default) vs the
    serialized legacy lowering."""
    from jax.sharding import PartitionSpec as P

    from sparkdl_tpu.ops._dispatch import use_pallas

    if impl is None:
        impl = "flash" if use_pallas() else "dense"
    spec = P("data", "seq", None, None)
    if impl == "flash":
        fn = functools.partial(
            ring_flash_attention, axis_name="seq", causal=causal,
            interpret=interpret, overlap=overlap,
        )
    elif impl == "dense":
        fn = functools.partial(
            ring_self_attention, axis_name="seq", causal=causal,
            overlap=overlap,
        )
    else:
        raise ValueError(f"impl must be 'dense' or 'flash', got {impl!r}")

    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    ))
