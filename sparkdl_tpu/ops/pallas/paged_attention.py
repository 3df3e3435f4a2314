"""Paged-attention decode kernel: attend a single-step query over a
POOLED paged KV cache through per-row block tables, reading only the
pages a row actually owns.

The XLA fallback in the model (``llama.py`` paged decode) gathers the
whole logical view first — ``pool[tables]`` materializes a
(B, max_pages·page, Hkv, D) copy in HBM and then reads it again for
attention, plus a ``jnp.repeat`` copy of K/V for GQA. Decode is
HBM-bandwidth-bound, so that ~3x traffic is ~3x step time at capacity.
This kernel instead:

- prefetches the block table and per-row lengths as SCALARS
  (``PrefetchScalarGridSpec``) so each grid step's page index is known
  before the body runs, and the pipeline DMAs exactly ONE whole page
  (page, Hkv, D) of K and of V per (row, page) program — pages beyond
  a row's length are masked out, and rows share nothing;
- unrolls the kv heads of that page in the body and keeps each head's
  GQA query group (``rep`` query heads per kv head) in VMEM against
  it — no repeated K/V, the MXU sees a (rep, page) × (page, D) pair
  per head;
- accumulates in the numerically-stable flash form (running max +
  rescaled sums) across the sequential page axis in VMEM scratch.

vLLM's paged_attention (CUDA) and the jax-in-tree TPU port are the
published precedents for the scalar-prefetch pattern; this kernel is
written for THIS engine's pool layout (page-major (n_pages, page,
Hkv, D), dump-page 0 for padding junk — see models/serving.py).
"""

import os

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Pages DMA'd per grid step (registered tunable knob). Read ONCE at
# import: the kernel is traced inside jitted engine programs and env
# vars are not part of the jit cache key — a mid-process flip must
# never silently retune an already-traced program. The autotuner runs
# each trial in a fresh subprocess, so trials see their own value;
# per-call overrides go through ``pages_per_block=``.
_DEFAULT_PAGES_PER_BLOCK = int(
    os.environ.get("SPARKDL_TPU_PAGED_PAGES_PER_BLOCK", 1))


def _kernel(page, hkv, scale, n_grid, ppb):
    from jax.experimental import pallas as pl

    def kernel(tables_ref, lens_ref, q_ref, *refs):
        k_refs = refs[:ppb]
        v_refs = refs[ppb:2 * ppb]
        o_ref = refs[2 * ppb]
        acc_ref, m_ref, l_ref = refs[2 * ppb + 1:]
        b = pl.program_id(0)
        j = pl.program_id(1)

        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        length = lens_ref[b]

        # unrolled over the ppb page tiles of this grid step; each
        # logical page jj masks itself against the row length (jj*page
        # < length also implies jj < max_pages, so the clamped index
        # map for the ragged final step can never let a duplicate
        # page through)
        for t in range(ppb):
            jj = j * ppb + t

            @pl.when(jj * page < length)
            def _attend(t=t, jj=jj):
                pos = jj * page + jax.lax.broadcasted_iota(
                    jnp.int32, (1, page), 1)
                # a page tile carries every kv head; heads unroll here
                # and not over the grid (see kv_spec)
                for h in range(hkv):
                    q = q_ref[0, h]                   # (rep, D)
                    k = k_refs[t][0, :, h, :]         # (page, D)
                    v = v_refs[t][0, :, h, :]         # (page, D)
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ) * scale                         # (rep, page)
                    s = jnp.where(pos < length, s, NEG_INF)
                    m_prev = m_ref[h]                 # (rep, 1)
                    l_prev = l_ref[h]
                    m_new = jnp.maximum(
                        m_prev, s.max(axis=-1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    p = jnp.exp(s - m_new)            # (rep, page)
                    l_ref[h] = (
                        l_prev * alpha + p.sum(axis=-1, keepdims=True))
                    m_ref[h] = m_new
                    acc_ref[h] = (
                        acc_ref[h] * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        )
                    )

        @pl.when(j == n_grid - 1)
        def _finalize():
            o_ref[0] = (
                acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
            ).astype(o_ref.dtype)

    return kernel


def paged_attention_decode(q, k_pool, v_pool, tables, lens, *,
                           scale=None, interpret=False,
                           pages_per_block=None):
    """One decode step over the paged pool.

    Args:
      q: (B, H, D) — this step's queries, H = Hkv * rep (GQA).
      k_pool, v_pool: (n_pages, page, Hkv, D) pooled physical cache.
      tables: (B, max_pages) int32 block tables (unused slots may
        point anywhere valid — typically the dump page 0; they are
        masked by ``lens``).
      lens: (B,) int32 — number of visible tokens per row (the row's
        current position + 1: the just-written token attends to
        itself).
      pages_per_block: K/V page tiles DMA'd per grid step (default:
        the ``SPARKDL_TPU_PAGED_PAGES_PER_BLOCK`` knob). The pool's
        pages are physically discontiguous, so a wider step is not one
        bigger block — the pool rides the call once per tile, each
        with its own table-indexed BlockSpec, and the kernel unrolls
        over the tiles. More pages per step amortize grid overhead at
        long contexts; the tradeoff is VMEM and is device-shaped,
        which is why it is an autotuner target.
    Returns: (B, H, D) attention output in q.dtype.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    n_pages, page, hkv, dk = k_pool.shape
    assert dk == d and h % hkv == 0, (q.shape, k_pool.shape)
    rep = h // hkv
    max_pages = tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    ppb = int(pages_per_block or _DEFAULT_PAGES_PER_BLOCK)
    ppb = max(1, min(ppb, max_pages))

    qg = q.reshape(b, hkv, rep, d)
    tables = tables.astype(jnp.int32)
    lens = lens.astype(jnp.int32)

    n_grid = pl.cdiv(max_pages, ppb)
    grid = (b, n_grid)
    # index maps see (grid..., *scalar_prefetch_refs)
    q_spec = pl.BlockSpec(
        (1, hkv, rep, d), lambda bi, j, tbl, ln: (bi, 0, 0, 0))

    def kv_spec(t):
        # tile t of a grid step covers logical page j*ppb + t; the
        # ragged final step clamps the table column (the duplicate
        # reads it causes are masked in-kernel by the lens check).
        # The tile is the WHOLE page, all kv heads: a one-head block
        # (1, page, 1, D) breaks the TPU's (8, 128) rule on the pool's
        # minor (Hkv, D) dims, and a page is contiguous in HBM anyway.
        def index(bi, j, tbl, ln, t=t):
            jj = jnp.minimum(j * ppb + t, max_pages - 1)
            return (tbl[bi, jj], 0, 0, 0)

        return pl.BlockSpec((1, page, hkv, d), index)

    out_spec = pl.BlockSpec(
        (1, hkv, rep, d), lambda bi, j, tbl, ln: (bi, 0, 0, 0))

    out = pl.pallas_call(
        _kernel(page, hkv, scale, n_grid, ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=([q_spec]
                      + [kv_spec(t) for t in range(ppb)]
                      + [kv_spec(t) for t in range(ppb)]),
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, rep, d), jnp.float32),   # acc
                pltpu.VMEM((hkv, rep, 1), jnp.float32),   # running max
                pltpu.VMEM((hkv, rep, 1), jnp.float32),   # running sum
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sparkdl_paged_decode",
    )(tables, lens, qg, *([k_pool] * ppb), *([v_pool] * ppb))
    return out.reshape(b, h, d)


def paged_attention_decode_sharded(mesh, *, axis_name="model",
                                   scale=None, interpret=False,
                                   pages_per_block=None):
    """Bind the paged decode kernel to a TP mesh: the pool is sharded
    over its kv-head axis on ``axis_name`` (exactly the serving
    engine's cache sharding) and each device runs the kernel on its
    LOCAL kv heads — every kv head's GQA query group is co-resident
    with it, so the shard_map needs no collectives at all; the o_proj
    that follows does the psum, same as the gather path.

    Returns ``f(q, k_pool, v_pool, tables, lens)`` on GLOBAL arrays:
    q (B, H, D) sharded over heads, pools (P, page, Hkv, D) sharded
    over kv heads, tables/lens replicated."""
    from jax.sharding import PartitionSpec as P

    def local_fn(q, k_pool, v_pool, tables, lens):
        return paged_attention_decode(
            q, k_pool, v_pool, tables, lens, scale=scale,
            interpret=interpret, pages_per_block=pages_per_block,
        )

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, axis_name, None),
                  P(None, None, axis_name, None),
                  P(None, None, axis_name, None),
                  P(), P()),
        out_specs=P(None, axis_name, None),
        check_vma=False,
    )
