"""Speculative decoding: a cheap DRAFT model proposes k tokens, the
target model verifies them in ONE forward, and the longest agreeing
prefix is accepted — greedy outputs are EXACTLY the target model's own
greedy decode, independent of the draft (blockwise-parallel /
speculative-decoding identity for argmax sampling).

TPU-first framing:

- The draft's k-step loop and the target's (k+1)-token verify are each
  ONE jitted program; Python touches the loop once per ROUND, so the
  host's dispatch and readback are paid per ~k tokens instead of per
  token — speculation helps the dispatch bound, not just the HBM
  bound (what a dispatch costs on the chip: not measured).
- The natural draft here is the int8 weight-only tree of the SAME
  model (models/quant.py): decode is HBM-bound, so the draft streams
  half the bytes; no second architecture to maintain, and acceptance
  is high because int8 argmax mostly matches bf16.
- Rejected speculation rewinds both KV caches by resetting the cache
  index — the shared-index decode branch (models/llama.py) writes
  position p before attending to it, so stale rows beyond the index
  are invisible and get overwritten on the next pass.

Reference: no counterpart (the reference is a training-launcher stub);
this extends the serving story of SURVEY.md §2's model zoo.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


def set_cache_index(cache, idx):
    """Rewind/advance every layer's shared cache index (rejected
    speculation). Stale K/V rows beyond ``idx`` are harmless: the
    decode branch writes a position before attending to it."""
    idx = jnp.asarray(idx, jnp.int32)

    def leaf(path, x):
        name = str(getattr(path[-1], "key", ""))
        return jnp.broadcast_to(idx, x.shape) if name == "cache_index" else x

    return jax.tree_util.tree_map_with_path(leaf, cache)


@functools.lru_cache(maxsize=32)
def _spec_programs(target_cfg, draft_cfg, k):
    from sparkdl_tpu.models.llama import Llama

    target = Llama(target_cfg)
    draft = Llama(draft_cfg)

    @jax.jit
    def prefill(params, d_params, prompt):
        """Both caches filled with the prompt; first token from the
        target (greedy). The draft's logits are discarded — its cache
        just has to be position-synced."""
        logits, st = target.apply(
            {"params": params}, prompt, mutable=["cache"])
        _, dst = draft.apply(
            {"params": d_params}, prompt, mutable=["cache"])
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return st["cache"], dst["cache"], tok

    @jax.jit
    def propose(d_params, d_cache, token, pos):
        """Draft scans k greedy steps from ``token``; returns its
        proposals (B, k) and the advanced draft cache. The rewind to
        ``pos`` (rejected speculation from the previous round) happens
        IN-GRAPH so the whole round stays one dispatch. A final
        logits-discarded step writes d_k's K/V so a fully-accepted
        round leaves the draft cache whole up to the bonus token."""
        d_cache = set_cache_index(d_cache, pos)

        def body(carry, _):
            cache, tok = carry
            logits, st = draft.apply(
                {"params": d_params, "cache": cache}, tok[:, None],
                mutable=["cache"],
            )
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (st["cache"], nxt), nxt

        (d_cache, last), toks = jax.lax.scan(
            body, (d_cache, token), None, length=k)
        _, st = draft.apply(
            {"params": d_params, "cache": d_cache}, last[:, None],
            mutable=["cache"],
        )
        return st["cache"], toks.T  # (B, k)

    @jax.jit
    def verify(params, cache, token, proposals, pos):
        """ONE target forward over [token, d_1..d_k] (k+1 positions)
        from (in-graph-rewound) index ``pos``: logits[i] predicts the
        token after position i. Returns the target's greedy choice at
        every position (B, k+1) and the advanced target cache."""
        cache = set_cache_index(cache, pos)
        seq = jnp.concatenate([token[:, None], proposals], axis=1)
        logits, st = target.apply(
            {"params": params, "cache": cache}, seq, mutable=["cache"],
        )
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return st["cache"], greedy

    return prefill, propose, verify


def speculative_generate(model, params, draft_params, prompt_tokens, *,
                         max_new_tokens=32, k=None, draft_model=None,
                         eos_id=None):
    """Greedy generation with draft-model speculation. Returns
    ``(tokens, stats)``: tokens exactly as :func:`generate` (greedy)
    would produce, ``stats`` = {"rounds", "proposed", "accepted"}.

    :param k: draft length (tokens proposed per verify round). Default
        ``None`` resolves ``SPARKDL_TPU_SPEC_DRAFT_K`` (registered in
        :mod:`sparkdl_tpu.utils.knobs`; 4 when unset) — the env knob
        an autotuned profile pins per device kind. An explicit ``k``
        always wins.
    :param draft_model: model for ``draft_params`` (default: the
        target architecture — e.g. int8 weights of the same model via
        ``dataclasses.replace(cfg, quant="int8")``).
    """
    if k is None:
        from sparkdl_tpu.utils.knobs import read_int

        k = read_int("SPARKDL_TPU_SPEC_DRAFT_K", 4)
    prompt_tokens = jnp.asarray(prompt_tokens, jnp.int32)
    b, p_len = prompt_tokens.shape
    cfg = model.cfg
    # + k scratch: the last verify writes up to k positions past the
    # final accepted token, and a clamped dynamic_update_slice would
    # silently corrupt earlier rows (breaking the exactness guarantee)
    if p_len + max_new_tokens + k > cfg.max_cache_len:
        raise ValueError(
            f"prompt ({p_len}) + max_new_tokens ({max_new_tokens}) "
            f"+ k ({k}) speculation scratch exceeds max_cache_len "
            f"({cfg.max_cache_len}); raise max_cache_len or lower k"
        )
    target_cfg = dataclasses.replace(cfg, decode=True)
    d_base = draft_model.cfg if draft_model is not None else cfg
    draft_cfg = dataclasses.replace(d_base, decode=True)
    if draft_cfg.max_cache_len < target_cfg.max_cache_len:
        draft_cfg = dataclasses.replace(
            draft_cfg, max_cache_len=target_cfg.max_cache_len)
    prefill, propose, verify = _spec_programs(target_cfg, draft_cfg, k)

    cache, d_cache, token = prefill(params, draft_params, prompt_tokens)
    new = [np.asarray(token)]          # list of (B,) accepted tokens
    n_new = 1
    pos = p_len                        # both caches sit at this index
    stats = {"rounds": 0, "proposed": 0, "accepted": 0}

    while n_new < max_new_tokens:
        # pos crosses as a device scalar: a Python int would be baked
        # in as a constant and retrace both programs every round
        pos_dev = jnp.asarray(pos, jnp.int32)
        d_cache, proposals = propose(draft_params, d_cache, token,
                                     pos_dev)
        cache, greedy = verify(params, cache, token, proposals, pos_dev)
        prop = np.asarray(proposals)           # (B, k)
        g = np.asarray(greedy)                 # (B, k+1)
        # longest prefix where the draft matched the target, over the
        # whole batch (lockstep: exactness requires every row agrees)
        agree = (prop == g[:, :k]).all(axis=0)
        m = int(np.argmin(agree)) if not agree.all() else k
        # accepted draft tokens + the target's own next token: the
        # verify forward already scored position m, so round output is
        # m+1 tokens — on full acceptance that's the k+1 'bonus'.
        step_tokens = [prop[:, i] for i in range(m)] + [g[:, m]]
        stats["rounds"] += 1
        stats["proposed"] += k
        stats["accepted"] += m
        take = min(len(step_tokens), max_new_tokens - n_new)
        new.extend(step_tokens[:take])
        n_new += take
        token = jnp.asarray(step_tokens[take - 1])
        # next round's programs rewind both caches to this in-graph
        pos = pos + m + 1
        if eos_id is not None:
            arr = np.stack(new[-take:], axis=1)
            hit = np.nonzero((arr == eos_id).all(axis=0))[0]
            if hit.size:
                overshoot = take - (int(hit[0]) + 1)
                if overshoot:
                    del new[len(new) - overshoot:]
                break

    toks = jnp.asarray(np.stack(new, axis=1), jnp.int32)  # (B, n)
    return jnp.concatenate([prompt_tokens, toks], axis=1), stats


def assemble_round(proposals, m, final):
    """Pack a speculation round's output: row b's tokens are
    ``proposals[b, :m[b]]`` then ``final[b]`` (bonus or correction),
    padded with zeros; counts = m+1. ONE definition shared by the
    greedy and sampling acceptance paths."""
    b, k = proposals.shape
    idx = jnp.arange(k + 1)[None]
    padded = jnp.pad(proposals, ((0, 0), (0, 1)))
    tokens = jnp.where(
        idx < m[:, None], padded,
        jnp.where(idx == m[:, None], final[:, None], 0),
    ).astype(jnp.int32)
    return tokens, m + 1


def spec_sample_tokens(q_probs, p_probs, proposals, rng):
    """Distribution-exact speculative ACCEPT/RESAMPLE (the sampling
    counterpart of the greedy longest-agreeing-prefix rule; Leviathan
    et al.'s rejection scheme). Pure function so the math is unit-
    testable against analytic marginals.

    Args:
      q_probs: (B, k, V) draft distributions at each proposal step.
      p_probs: (B, k+1, V) target distributions at the k+1 verified
        positions.
      proposals: (B, k) tokens the draft sampled (from q_probs).
      rng: PRNG key.
    Returns ``(tokens (B, k+1), counts (B,))``: row b's first
    ``counts[b]`` tokens are the round's output — accepted proposals
    followed by one resampled (on rejection, from the residual
    ``max(p-q, 0)``) or bonus (full acceptance, from the k+1-th
    target distribution) token. Marginals equal target-only sampling
    exactly; the draft moves only the acceptance rate.
    """
    b, k, _v = q_probs.shape
    rng_u, rng_r, rng_b = jax.random.split(rng, 3)
    px = jnp.take_along_axis(
        p_probs[:, :k], proposals[..., None], -1)[..., 0]   # (B, k)
    qx = jnp.take_along_axis(
        q_probs, proposals[..., None], -1)[..., 0]
    u = jax.random.uniform(rng_u, (b, k))
    accept = u * qx < px        # u < p(x)/q(x); q(x) > 0 (x ~ q)
    all_acc = accept.all(-1)
    m = jnp.where(all_acc, k, jnp.argmin(accept, -1))       # (B,)
    # residual distribution at the first rejected position (index
    # clamped for the gather; unused on full acceptance)
    mc = jnp.minimum(m, k - 1)
    p_m = jnp.take_along_axis(p_probs, mc[:, None, None], 1)[:, 0]
    q_m = jnp.take_along_axis(q_probs, mc[:, None, None], 1)[:, 0]
    resid = jnp.maximum(p_m - q_m, 0.0)
    # all-zero residual has probability 0 (it needs p<=q everywhere,
    # which makes rejection impossible); the floor only guards NaNs
    resid = resid / jnp.maximum(resid.sum(-1, keepdims=True), 1e-30)
    resampled = jax.random.categorical(
        rng_r, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1)
    bonus = jax.random.categorical(
        rng_b, jnp.log(jnp.maximum(p_probs[:, k], 1e-30)), axis=-1)
    final = jnp.where(all_acc, bonus, resampled).astype(jnp.int32)
    return assemble_round(proposals, m, final)
