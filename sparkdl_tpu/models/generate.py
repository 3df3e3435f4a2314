"""Autoregressive generation for the Llama decoder: prefill + cached
decode, greedy or temperature sampling. Serving-side counterpart to the
training path.

TPU-first design: the whole decode loop is ONE jitted program
(``lax.scan`` over steps) — per-token Python dispatch would pay a
host→device round trip per generated token. The jitted programs are
cached process-wide per (decode-config, temperature), so a serving
loop compiles on the first request only; jit's own static-argument
cache covers varying ``max_new_tokens``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp


NEG_INF = -1e30


def restrict_logits(logits, *, top_k=0, top_p=1.0):
    """Mask (..., V) TEMPERATURE-SCALED logits down to the sampling
    support: ``top_k`` keeps the k largest, ``top_p`` keeps the
    minimal sorted prefix whose mass reaches p (the top token always
    survives). Pure; shared by direct sampling and the speculative
    rejection scheme (which needs the restricted DISTRIBUTIONS, not
    just samples)."""
    l = logits.astype(jnp.float32)
    if top_k:
        kth = jax.lax.top_k(l, top_k)[0][..., -1:]
        l = jnp.where(l < kth, NEG_INF, l)
    if top_p < 1.0:
        sorted_l = jnp.sort(l, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        # keep entries whose cumulative mass BEFORE them is < p: the
        # first token always survives, the nucleus is the minimal
        # prefix reaching p
        before = jnp.cumsum(probs, axis=-1) - probs
        keep = before < top_p
        cutoff = jnp.min(
            jnp.where(keep, sorted_l, jnp.inf), axis=-1, keepdims=True)
        l = jnp.where(l < cutoff, NEG_INF, l)
    return l


def sample_logits(logits, rng, *, temperature, top_k=0, top_p=1.0):
    """One sampling step over (..., V) logits: greedy at temperature 0,
    else temperature-scaled categorical restricted by
    :func:`restrict_logits`. The single sampling definition for
    generate() and both serving engines."""
    return sample_logits_with_lp(logits, rng, temperature=temperature,
                                 top_k=top_k, top_p=top_p)[0]


def sample_logits_with_lp(logits, rng, *, temperature, top_k=0,
                          top_p=1.0):
    """(token, logprob): one sampling step plus the chosen token's
    logprob under the DISTRIBUTION ACTUALLY SAMPLED — the restricted
    temperature-scaled one (greedy reports the raw softmax logprob).
    The restriction is computed ONCE and both the draw and the score
    come from it, so tokens and their reported logprobs cannot
    desync."""
    if temperature == 0.0:
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lp_all = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    else:
        l = restrict_logits(logits.astype(jnp.float32) / temperature,
                            top_k=top_k, top_p=top_p)
        tok = jax.random.categorical(rng, l, axis=-1).astype(jnp.int32)
        lp_all = jax.nn.log_softmax(l, axis=-1)
    lp = jnp.take_along_axis(lp_all, tok[..., None], -1)[..., 0]
    return tok, lp


@functools.lru_cache(maxsize=64)
def _decode_programs(dec_cfg, temperature, top_k=0, top_p=1.0):
    """(prefill, decode_loop) jitted for one decode config. Cached so a
    second generate() call with the same config compiles nothing."""
    from sparkdl_tpu.models.llama import Llama

    dec_model = Llama(dec_cfg)

    def _next_token(logits, rng):
        return sample_logits_with_lp(
            logits, rng, temperature=temperature, top_k=top_k,
            top_p=top_p)

    @jax.jit
    def prefill(params, tokens, rng):
        logits, state = dec_model.apply(
            {"params": params}, tokens, mutable=["cache"],
        )
        rng, sub = jax.random.split(rng)
        token, lp = _next_token(logits[:, -1], sub)
        return state["cache"], token, lp, rng

    @functools.partial(jax.jit, static_argnums=(4,))
    def decode_loop(params, cache, token, rng, n_steps):
        def body(carry, _):
            cache, token, rng = carry
            logits, state = dec_model.apply(
                {"params": params, "cache": cache}, token[:, None],
                mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt, lp = _next_token(logits[:, -1], sub)
            return (state["cache"], nxt, rng), (nxt, lp)

        (cache, token, rng), (toks, lps) = jax.lax.scan(
            body, (cache, token, rng), None, length=n_steps
        )
        return cache, toks, lps  # (n_steps, batch) each

    return prefill, decode_loop


def generate(model, params, prompt_tokens, *, max_new_tokens=32,
             temperature=0.0, top_k=0, top_p=1.0, rng=None,
             eos_id=None, return_logprobs=False):
    """Generate continuations.

    :param model: a Llama (training or decode config — a decode-mode
        twin is derived automatically; params are shared).
    :param prompt_tokens: (batch, prompt_len) int32.
    :param top_k: sample only among the k most likely tokens (0 = all).
    :param top_p: nucleus sampling — the minimal top mass kept
        (1.0 = all). Both restrictions need ``temperature > 0``.
    :param return_logprobs: also return (batch, n) logprobs of the
        generated tokens under the distribution actually sampled
        (the serving engines' convention).
    :return: (batch, prompt_len + n) tokens, n <= max_new_tokens
        (shorter when every row has emitted ``eos_id``); with
        ``return_logprobs`` a ``(tokens, logprobs)`` pair.
    """
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    b, p_len = prompt_tokens.shape
    cfg = model.cfg
    if p_len + max_new_tokens > cfg.max_cache_len:
        raise ValueError(
            f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds max_cache_len ({cfg.max_cache_len}); raise "
            "LlamaConfig.max_cache_len"
        )
    dec_cfg = dataclasses.replace(cfg, decode=True)
    prefill, decode_loop = _decode_programs(
        dec_cfg, float(temperature), int(top_k), float(top_p))
    if rng is None:
        rng = jax.random.PRNGKey(0)

    cache, token, lp0, rng = prefill(params, prompt_tokens, rng)
    if max_new_tokens > 1:
        _, scanned, lps = decode_loop(
            params, cache, token, rng, max_new_tokens - 1
        )
        new_tokens = jnp.concatenate(
            [token[:, None], scanned.T], axis=1
        )  # (b, max_new_tokens)
        new_lps = jnp.concatenate([lp0[:, None], lps.T], axis=1)
    else:
        new_tokens = token[:, None]
        new_lps = lp0[:, None]

    if eos_id is not None:
        # Early-stop semantics of a step-by-step loop: truncate after
        # the first LOOP step where every row emitted eos. The prefill
        # token (column 0) is exempt — the loop formulation only checks
        # tokens its body generates. Tokens before the cut are
        # identical either way (decoding is causal and the per-step
        # rng split order is fixed), so scanning the full length and
        # trimming is observationally equivalent.
        import numpy as np

        all_eos = np.asarray((new_tokens[:, 1:] == eos_id).all(axis=0))
        hits = np.flatnonzero(all_eos)
        if hits.size:
            new_tokens = new_tokens[:, :int(hits[0]) + 2]
            new_lps = new_lps[:, :new_tokens.shape[1]]

    out = jnp.concatenate([prompt_tokens, new_tokens], axis=1)
    if return_logprobs:
        return out, new_lps
    return out
