"""Attention dispatch: pallas flash kernels on TPU, XLA reference
elsewhere, with padding and layout handling.

Public shape convention matches the models: (batch, seq, heads,
head_dim). Both directions are fused pallas kernels: the forward saves
only the per-row logsumexp, and the custom_vjp backward recomputes
probabilities tile-by-tile (dq kernel + dk/dv kernel) — O(S·D) memory
for training end to end.
"""

import functools
import math
import os

import jax

from sparkdl_tpu.ops._dispatch import block_for, pad_to as _pad_to, use_pallas as _use_pallas
from sparkdl_tpu.parallel.ring_attention import attention_reference

# Process-level default tiles, read ONCE at import (see
# flash_attention's docstring for why a trace-time env read would be a
# footgun). The per-dimension q/kv tiles are the autotuner's targets
# (registered tunable knobs); unset they inherit the legacy square
# block.
_DEFAULT_FLASH_BLOCK = int(os.environ.get("SPARKDL_TPU_FLASH_BLOCK", 128))
_DEFAULT_FLASH_BLOCK_Q = int(
    os.environ.get("SPARKDL_TPU_FLASH_BLOCK_Q", 0)) or _DEFAULT_FLASH_BLOCK
_DEFAULT_FLASH_BLOCK_KV = int(
    os.environ.get("SPARKDL_TPU_FLASH_BLOCK_KV", 0)) or _DEFAULT_FLASH_BLOCK


# custom_vjp over the PADDED (B, H, S, D) core: both forward and
# backward are fused pallas kernels; padding/layout transforms sit
# outside and differentiate through standard XLA transposes.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, scale, bq, bk, interpret):
    from sparkdl_tpu.ops.pallas.flash_attention import flash_attention_bhsd

    return flash_attention_bhsd(
        q, k, v, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=interpret,
    )


def _flash_core_fwd(q, k, v, causal, scale, bq, bk, interpret):
    from sparkdl_tpu.ops.pallas.flash_attention import flash_attention_bhsd

    o, lse = flash_attention_bhsd(
        q, k, v, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=interpret, return_lse=True,
    )
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, scale, bq, bk, interpret, res, do):
    import jax.numpy as jnp

    from sparkdl_tpu.ops.pallas.flash_attention import (
        flash_attention_bwd_bhsd,
    )

    q, k, v, o, lse = res
    # keepdims: lse/delta ride (B, H, S, 1) blocks (TPU tiling, see
    # flash_attention_bhsd docstring).
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    dq, dk, dv = flash_attention_bwd_bhsd(
        q, k, v, do, lse, delta, causal=causal, scale=scale,
        bq=bq, bk=bk, interpret=interpret,
    )
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, *, causal=True, scale=None, interpret=None,
                    block=None, block_q=None, block_kv=None):
    """Fused attention on (batch, seq, heads, head_dim) tensors —
    pallas forward AND backward on TPU (or ``interpret=True`` for
    tests); XLA reference elsewhere.

    ``block``: square q/k tile size (larger tiles amortize K/V
    streaming and widen the per-program matmuls at short seq).
    ``block_q`` / ``block_kv`` override the q and kv tiles
    independently — the shapes the autotuner searches via the
    ``SPARKDL_TPU_FLASH_BLOCK_Q`` / ``SPARKDL_TPU_FLASH_BLOCK_KV``
    knobs. All tile defaults are read ONCE at import — callers are
    jitted and env vars are not part of the jit cache key, so a
    mid-process env change must never silently retune (or fail to
    retune) an already-traced program. Sweeps pass tiles explicitly
    (via ``LlamaConfig.flash_block``), which changes the traced call
    and therefore the cache key.
    """
    if interpret is None:
        if not _use_pallas():
            return attention_reference(q, k, v, causal=causal, scale=scale)
        interpret = False
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    s = qt.shape[2]
    tile_q = int(block_q) if block_q else (
        int(block) if block else _DEFAULT_FLASH_BLOCK_Q)
    tile_kv = int(block_kv) if block_kv else (
        int(block) if block else _DEFAULT_FLASH_BLOCK_KV)
    bq = block_for(s, tile=tile_q)
    bk = block_for(s, tile=tile_kv)
    # the kernel needs the (padded) seq divisible by BOTH tiles
    mult = bq * bk // math.gcd(bq, bk)
    qt, pad = _pad_to(qt, mult, 2)
    if pad and not causal:
        # padded keys must not receive attention weight: causal masking
        # excludes them (queries come first); the bidirectional kernel
        # has no key mask. Compiled for the TPU that is an error, not a
        # quiet switch to the reference path; the interpreted kernel
        # (tests) keeps the reference answer.
        if not interpret:
            raise ValueError(
                f"bidirectional flash attention needs a sequence that "
                f"is a multiple of its tiles ({bq}, {bk}), got {s}: "
                "pad it, or call attention_reference")
        return attention_reference(q, k, v, causal=False, scale=scale)
    kt, _ = _pad_to(kt, mult, 2)
    vt, _ = _pad_to(vt, mult, 2)
    out = _flash_core(qt, kt, vt, causal, scale, bq, bk, interpret)
    if pad:
        out = out[:, :, :s, :]
    return out.transpose(0, 2, 1, 3)
