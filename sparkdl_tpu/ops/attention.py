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

# Process-level tile overrides, read ONCE at import (see
# flash_attention's docstring for why a trace-time env read would be a
# footgun). Unset (0), the tiles are chosen from the shape by
# ``pallas.flash_attention.flash_tiles``; the per-dimension q/kv
# variables win over the legacy square block.
_DEFAULT_FLASH_BLOCK = int(os.environ.get("SPARKDL_TPU_FLASH_BLOCK", 0))
_DEFAULT_FLASH_BLOCK_Q = int(
    os.environ.get("SPARKDL_TPU_FLASH_BLOCK_Q", 0)) or _DEFAULT_FLASH_BLOCK
_DEFAULT_FLASH_BLOCK_KV = int(
    os.environ.get("SPARKDL_TPU_FLASH_BLOCK_KV", 0)) or _DEFAULT_FLASH_BLOCK


# custom_vjp over the PADDED (B, H, S, D) core: both forward and
# backward are fused pallas kernels; padding/layout transforms sit
# outside and differentiate through standard XLA transposes.


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, scale, bq, bk, interpret, window):
    from sparkdl_tpu.ops.pallas.flash_attention import flash_attention_bhsd

    return flash_attention_bhsd(
        q, k, v, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=interpret, window=window,
    )


def _flash_core_fwd(q, k, v, causal, scale, bq, bk, interpret, window):
    from sparkdl_tpu.ops.pallas.flash_attention import flash_attention_bhsd

    o, lse = flash_attention_bhsd(
        q, k, v, causal=causal, scale=scale, bq=bq, bk=bk,
        interpret=interpret, return_lse=True, window=window,
    )
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, scale, bq, bk, interpret, window, res, do):
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
        bq=bq, bk=bk, interpret=interpret, window=window,
    )
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    interpret=None, block=None, block_q=None,
                    block_kv=None):
    """Fused attention on (batch, seq, heads, head_dim) tensors —
    pallas forward AND backward on TPU (or ``interpret=True`` for
    tests); XLA reference elsewhere.

    ``window`` (static; causal only): a query at position i sees the
    keys j with ``0 <= i - j < window``, and the kernels walk only the
    tiles such a pair lies in. ``None``, or a window that covers the
    sequence, is the causal program.

    Left alone, each of the three kernels takes the tiles
    ``flash_tiles`` chooses from (seq, head_dim, dtype). ``block``
    forces one square q/k tile on all three; ``block_q`` /
    ``block_kv`` force the q and kv tiles independently — the shapes
    the autotuner searches via the ``SPARKDL_TPU_FLASH_BLOCK_Q`` /
    ``SPARKDL_TPU_FLASH_BLOCK_KV`` knobs. Those variables are read
    ONCE at import — callers are jitted and env vars are not part of
    the jit cache key, so a mid-process env change must never silently
    retune (or fail to retune) an already-traced program. Sweeps pass
    tiles explicitly (via ``LlamaConfig.flash_block``), which changes
    the traced call and therefore the cache key.
    """
    if interpret is None:
        if not _use_pallas():
            return attention_reference(q, k, v, causal=causal, scale=scale,
                                       window=window)
        interpret = False
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    s = qt.shape[2]
    bq = int(block_q or block or _DEFAULT_FLASH_BLOCK_Q) or None
    bk = int(block_kv or block or _DEFAULT_FLASH_BLOCK_KV) or None
    # the kernels need the (padded) seq divisible by every tile: by a
    # forced one as given, and by 128 where the rule is to choose (its
    # tiles are power-of-two multiples of that which divide the seq).
    # A seq under 128 is one tile whatever was asked for.
    mult = block_for(s) if s < 128 else math.lcm(bq or 128, bk or 128)
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
                f"is a multiple of its tiles ({mult}), got {s}: "
                "pad it, or call attention_reference")
        return attention_reference(q, k, v, causal=False, scale=scale)
    kt, _ = _pad_to(kt, mult, 2)
    vt, _ = _pad_to(vt, mult, 2)
    out = _flash_core(qt, kt, vt, causal, scale, bq, bk, interpret, window)
    if pad:
        out = out[:, :, :s, :]
    return out.transpose(0, 2, 1, 3)
