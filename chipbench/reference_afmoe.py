"""The plain reference of a decoder that mixes window and full
attention layers over gated routed experts (``afmoe``: Trinity-Mini):
in straightforward ``jax.numpy`` and float32 at the highest matmul
precision. No kernel, no sorted dispatch and no module of the program:
it takes the program's weight TREE (the names below) and nothing else
of it. What it shares with ``reference_mla.py`` (the norm, a
projection with its adapter, rope, the gated MLP, the sigmoid router
and the expert layer with every expert held, the loss a block of
tokens at a time) is imported from there, not copied.

    embed/embedding, final_norm/scale, lm_head/kernel, and a mixer
    layer_<i>/{norm,post_norm}/scale with ONE of
      attn/{q_proj,k_proj,v_proj,gate_proj,o_proj}/kernel
          (+ lora_a, lora_b), {q_norm,k_norm}/scale
      mlp/{gate_proj,up_proj,down_proj}/kernel
      moe/{router,shared_gate,shared_up,shared_down}/kernel,
          router_bias, w_gate_up (an expert's gate | up, side by side),
          w_down

Equations (ISSUE 35; docs/hybrid.rst). ``x0 = E[ids] * sqrt(hidden)``.
A published layer is two residual steps, each ``x + RMSNorm_post(
mixer(RMSNorm_pre(x)))``: attention, then the dense MLP in the first
``num_dense_layers`` layers and the expert layer in every other.

- Attention: ``q = W_q h`` (heads of ``head_dim``), ``k = W_k h``,
  ``v = W_v h`` (key/value heads), ``g = W_g h``; an RMSNorm over a
  head's ``head_dim`` on q and on k, ONE weight vector for all heads;
  on ``sliding_attention`` layers rope on q and k (every dimension
  turns), on ``full_attention`` layers no positions at all; scores
  ``q k^T / sqrt(head_dim)`` where ``0 <= i - j`` and, on a window
  layer, ``i - j < sliding_window`` (the mask is written out), plain
  softmax, a head at a time and a block of queries at a time, a query
  head reading key/value head ``head // (heads / kv heads)``;
  ``W_o (o * sigmoid(g))``.
- Dense MLP and experts: ``reference_mla``'s (``s = sigmoid(W_r h)``,
  the top k of ``s + b``, weights ``route_scale * s_k / (sum + 1e-20)``,
  every expert held on every token, plus the shared expert).

Departures, each as the program has it and as the configuration file's
``assumed`` says: rope pairs dimension ``i`` with ``i + head_dim / 2``
(the family's checkpoints pair the same halves or ``2i`` with
``2i + 1``: on seeded weights a fixed permutation of q_proj's and
k_proj's columns); the output gate, the norms a head, rope on the
window layers only, the four norms a layer and the embedding's scale
are the family's modelling code's, not keys of ``config.json``; the
router's bias is a frozen drawn vector.

``round_to`` (``arch_of``) is the CONTROL: with a dtype's name there,
every projection's input and weights are rounded to it before the
product, which a comparison that holds the program to bfloat16 must
refuse.
"""

import functools
import types

import jax
import jax.numpy as jnp

from chipbench import reference_mla
from chipbench.reference_mla import _f32, _proj, _rms, _rope

QUERY_BLOCK = 1024  # queries a block of scores: 1024 x 8192 a head


def _attend(q, k, v, scale, window):
    """One head: q, k, v (b, s, d); query i sees key j where
    ``0 <= i - j`` and, with a `window`, ``i - j < window``; a block of
    queries at a time so that a block's scores are all that is held."""
    b, s, d = q.shape
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    @jax.checkpoint
    def some(at):
        q, rows = at                                   # (b, block, d)
        scores = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        back = rows[:, None] - keys[None, :]           # i - j
        seen = back >= 0
        if window is not None:
            seen = seen & (back < window)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1) @ v

    o = jax.lax.map(some, (
        jnp.moveaxis(q.reshape(b, -1, block, d), 1, 0),
        keys.reshape(-1, block)))
    return jnp.moveaxis(o, 0, 1).reshape(b, s, -1)


def _attention(p, x, *, window, arch):
    b, s, _ = x.shape
    heads, kv_heads, head = arch["heads"], arch["kv_heads"], arch["head_dim"]
    q = _proj(p["q_proj"], x, arch).reshape(b, s, heads, head)
    k = _proj(p["k_proj"], x, arch).reshape(b, s, kv_heads, head)
    v = _proj(p["v_proj"], x, arch).reshape(b, s, kv_heads, head)
    gate = _proj(p["gate_proj"], x, arch)
    q = _rms(q, p["q_norm"]["scale"], arch["eps"])
    k = _rms(k, p["k_norm"]["scale"], arch["eps"])
    if window is not None:
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    # query head h reads key/value head h // (heads / kv_heads)
    k, v = (jnp.repeat(a, heads // kv_heads, 2) for a in (k, v))

    @jax.checkpoint
    def head_(qkv):
        return _attend(*qkv, head ** -0.5, window)

    o = jax.lax.map(head_, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    o = jnp.moveaxis(o, 0, 2).reshape(b, s, -1)
    return _proj(p["o_proj"], o * jax.nn.sigmoid(gate), arch)


def _layer(p, x, *, window, arch):
    """One residual step. `window` is the step's own (static): the
    configuration's for an attention mixer of a ``sliding_attention``
    layer, None for every other."""
    h = _rms(x, p["norm"]["scale"], arch["eps"])
    if "attn" in p:
        h = _attention(_f32(p["attn"]), h, window=window, arch=arch)
    elif "mlp" in p:
        h = reference_mla._mlp(p["mlp"], h, arch=arch)
    else:
        h = reference_mla._experts(p["moe"], h, arch=arch)
    return x + _rms(h, p["post_norm"]["scale"], arch["eps"])


def arch_of(cfg, lora_alpha=16.0, lora_rank=8, round_to=None):
    """What the equations need of a configuration file's dict (the
    published key names), hashable: it keys the jitted programs. With
    ``reference_mla``'s keys for what is imported from there."""
    return tuple(sorted({
        "heads": cfg["num_attention_heads"],
        "kv_heads": cfg["num_key_value_heads"], "head_dim": cfg["head_dim"],
        "window": cfg["sliding_window"],
        "layer_types": tuple(cfg["layer_types"]),
        "embed_scale": (float(cfg["hidden_size"]) ** 0.5
                        if cfg["mup_enabled"] else 1.0),
        "rope_theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["route_scale"]),
        "first_expert": 0, "eps": float(cfg["rms_norm_eps"]),
        "lora_scale": lora_alpha / lora_rank, "round_to": round_to}.items()))


def _window_of(params, i, arch):
    """The window of mixer `i`: the tree holds two mixers a published
    layer, attention first."""
    if "attn" not in params[f"layer_{i}"]:
        return None
    kind = arch["layer_types"][i // 2]
    return arch["window"] if kind == "sliding_attention" else None


@functools.lru_cache(maxsize=None)
def _programs(arch, window):
    arch = dict(arch)
    layer = functools.partial(_layer, window=window, arch=arch)

    @jax.jit
    def fwd(p, x):
        with jax.default_matmul_precision("highest"):
            return layer(p, x)

    @jax.jit
    def picks(p, x):
        with jax.default_matmul_precision("highest"):
            return reference_mla._route(
                p["moe"], _rms(x, p["norm"]["scale"], arch["eps"]),
                arch=arch)[0]

    @jax.jit
    def bwd(p, x, g):
        """(sum of squares of the adapter gradients, dx) of one mixer:
        differentiated in the adapters and the input only, so no
        gradient of a frozen matrix is ever held."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(p)
        leaves = [leaf for _, leaf in flat]
        lora = ["lora_" in jax.tree_util.keystr(path) for path, _ in flat]

        def of(adapters, x):
            it = iter(adapters)
            return layer(treedef.unflatten(
                [next(it) if a else leaf for leaf, a in zip(leaves, lora)]), x)

        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(
                of, [leaf for leaf, a in zip(leaves, lora) if a], x)
            d_adapters, dx = vjp(g)
        square = sum((jnp.sum(jnp.square(d.astype(jnp.float32)))
                      for d in d_adapters), jnp.zeros((), jnp.float32))
        return square, dx

    @jax.jit
    def embed(table, tokens):
        return table.astype(jnp.float32)[tokens] * arch["embed_scale"]

    return types.SimpleNamespace(fwd=fwd, bwd=bwd, picks=picks, embed=embed)


def _forward(params, tokens, arch):
    """Inputs of every mixer and the last one's output."""
    d = dict(arch)
    xs = [_programs(arch, None).embed(params["embed"]["embedding"], tokens)]
    for i in range(reference_mla._mixers(params)):
        xs.append(_programs(arch, _window_of(params, i, d)).fwd(
            params[f"layer_{i}"], xs[-1]))
    return xs


def loss_and_adapter_grad_norm(params, tokens, targets, arch):
    """Mean cross-entropy of `targets` after `tokens`, the global norm
    of its gradient over every ``lora_a``/``lora_b`` leaf, and for each
    expert layer the experts every token chose, ``{layer: (tokens,
    picks)}``: what the program's own choice is held against."""
    d = dict(arch)
    mixers = reference_mla._mixers(params)
    xs = _forward(params, tokens, arch)
    chosen = {i: _programs(arch, None).picks(params[f"layer_{i}"], xs[i])
              for i in range(mixers) if "moe" in params[f"layer_{i}"]}
    # the final norm, the head and the loss, a block of tokens at a time
    loss, g = reference_mla._loss_and_grad(params, xs.pop(), targets, arch)
    square = jnp.zeros((), jnp.float32)
    for i in reversed(range(mixers)):
        layer_square, g = _programs(arch, _window_of(params, i, d)).bwd(
            params[f"layer_{i}"], xs.pop(), g)
        square = square + layer_square
    return loss, float(jnp.sqrt(square)), chosen


def logits(params, tokens, arch):
    """Float32 logits of the full causal forward over `tokens`."""
    return reference_mla._programs(arch).logits(
        params["final_norm"]["scale"], params["lm_head"]["kernel"],
        _forward(params, tokens, arch)[-1])
