"""The plain reference of a latent-attention expert decoder
(``glm4_moe_lite``: GLM-4.7-Flash): in straightforward ``jax.numpy``
and float32 at the highest matmul precision. No kernel, no sorted
dispatch, no fused matrices' product and no module of the program: it
takes the program's weight TREE (the names below) and nothing else of
it.

    embed/embedding, final_norm/scale, lm_head/kernel, and a mixer
    layer_<i>/norm/scale with ONE of
      mla/{q_a_proj,q_b_proj,kv_a_proj_with_mqa,kv_b_proj,o_proj}/kernel
          (+ lora_a, lora_b), {q_a,kv_a}_layernorm/scale
      mlp/{gate_proj,up_proj,down_proj}/kernel
      moe/{router,shared_gate,shared_up,shared_down}/kernel,
          router_bias, w_gate_up (an expert's gate | up, side by side),
          w_down

Equations (ISSUE 33, section 1; docs/hybrid.rst): a published layer is
two mixers, each ``x + mixer(RMSNorm(x))``: attention, then the dense
MLP in the first ``first_k_dense_replace`` layers and the expert layer
in every other.

- Latent attention: ``c_q = RMSNorm(W_qa x)``, ``[q_nope | q_rope] =
  W_qb c_q`` a head; ``[c_kv | k_rope] = W_kva x``, ``c_kv =
  RMSNorm(c_kv)``, ``[k_nope | v] = W_kvb c_kv`` a head; rope on
  ``q_rope`` a head and on ``k_rope``, ONE vector a token that every
  head shares; scores ``[q_nope | q_rope] [k_nope | k_rope]^T /
  sqrt(nope + rope)``, causal, plain softmax, a head at a time and a
  block of queries at a time; ``W_o`` of the heads' values.
- Dense MLP: ``W_down (silu(W_gate x) * W_up x)``.
- Experts: ``s = sigmoid(W_r x)``; the top k of ``s + b``; weights
  ``scale * s_k / (sum of the chosen s + 1e-20)``; every expert HELD on
  every token, ``W2 (silu(Wg x) * Wu x)``, with its weight as a mask
  (zero where it was not chosen), plus the shared expert of the same
  form.

Departures, each as the program has it and as the configuration file's
``assumed`` says: rope pairs dimension ``i`` with ``i + rope / 2`` (the
family's checkpoints pair ``2i`` with ``2i + 1``: on seeded weights a
fixed permutation of the rope columns); no query/key norm a head; no
multi-token-prediction module.

``round_to`` (``arch_of``) is the CONTROL: with a dtype's name there,
every projection's input and weights are rounded to it before the
product, which a comparison that holds the program to bfloat16 must
refuse.

One mixer's weights are upcast at a time, an expert's as it is used,
and the backward recomputes each mixer from its saved input, so the
reference fits beside the live bf16 model on the chip.
"""

import functools
import types

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024  # queries a block of scores: 1024 x 8192 a head
LOSS_BLOCK = 512    # tokens a block of logits: 512 x 154880


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    scale = scale.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mm(x, w, arch):
    """``x @ w`` in float32, or both rounded to the control's dtype
    first."""
    w = w.astype(jnp.float32)
    if arch["round_to"]:
        x, w = (a.astype(arch["round_to"]).astype(jnp.float32)
                for a in (x, w))
    return x @ w


def _proj(p, x, arch):
    y = _mm(x, p["kernel"], arch)
    if "lora_a" in p:
        y = y + arch["lora_scale"] * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def _rope(x, theta):
    """x (b, s, heads, r): dimension i turns with i + r / 2, position t
    by ``t / theta^(2i / r)``."""
    s, r = x.shape[1], x.shape[-1]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] / (
        theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attend(q, k, v, scale):
    """One head: q and k (b, s, d), v (b, s, dv); causal, a block of
    queries at a time so that a block's scores are all that is held."""
    b, s, d = q.shape
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    @jax.checkpoint
    def some(at):
        q, rows = at                                   # (b, block, d)
        scores = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        seen = rows[:, None] >= keys[None, :]
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1) @ v

    o = jax.lax.map(some, (
        jnp.moveaxis(q.reshape(b, -1, block, d), 1, 0),
        keys.reshape(-1, block)))
    return jnp.moveaxis(o, 0, 1).reshape(b, s, -1)


def _attention(p, x, *, arch):
    b, s, _ = x.shape
    heads, nope, rope = arch["heads"], arch["qk_nope"], arch["qk_rope"]
    c_q = _rms(_proj(p["q_a_proj"], x, arch), p["q_a_layernorm"]["scale"],
               arch["eps"])
    q = _proj(p["q_b_proj"], c_q, arch).reshape(b, s, heads, nope + rope)
    kv_a = _proj(p["kv_a_proj_with_mqa"], x, arch)
    c_kv = _rms(kv_a[..., :-rope], p["kv_a_layernorm"]["scale"], arch["eps"])
    kv = _proj(p["kv_b_proj"], c_kv, arch).reshape(
        b, s, heads, nope + arch["v"])
    k_rope = _rope(kv_a[..., None, -rope:], arch["rope_theta"])
    q = jnp.concatenate(
        [q[..., :nope], _rope(q[..., nope:], arch["rope_theta"])], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_rope, heads, 2)], -1)
    v = kv[..., nope:]

    @jax.checkpoint
    def head(qkv):
        return _attend(*qkv, (nope + rope) ** -0.5)

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return _proj(p["o_proj"], jnp.moveaxis(o, 0, 2).reshape(b, s, -1), arch)


def _gated(x, gate, up, down, arch):
    return _mm(jax.nn.silu(_mm(x, gate, arch)) * _mm(x, up, arch), down, arch)


def _mlp(p, x, *, arch):
    return _gated(x, *(p[name]["kernel"] for name in (
        "gate_proj", "up_proj", "down_proj")), arch)


def _route(p, x, *, arch):
    """(idx, weights) of every token over ALL the routed experts."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"].astype(jnp.float32))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                           arch["top_k"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, arch["routed_scale"] * chosen / (
        chosen.sum(-1, keepdims=True) + 1e-20)


def _experts(p, x, *, arch):
    idx, weights = _route(p, x, arch=arch)
    d_ff = p["w_down"].shape[1]

    @jax.checkpoint
    def one(x, weights, e, gate_up, down):
        weight = jnp.where(idx == e, weights, 0.0).sum(-1, keepdims=True)
        return weight * _gated(
            x, gate_up[:, :d_ff], gate_up[:, d_ff:], down, arch)

    def add(out, expert):
        return out + one(x, weights, *expert), None

    held = p["w_down"].shape[0]
    routed, _ = jax.lax.scan(add, jnp.zeros_like(x), (
        arch["first_expert"] + jnp.arange(held), p["w_gate_up"], p["w_down"]))
    return routed + _gated(x, *(p[name]["kernel"] for name in (
        "shared_gate", "shared_up", "shared_down")), arch)


def _layer(p, x, *, arch):
    h = _rms(x, p["norm"]["scale"], arch["eps"])
    if "mla" in p:
        return x + _attention(_f32(p["mla"]), h, arch=arch)
    if "mlp" in p:
        return x + _mlp(p["mlp"], h, arch=arch)
    return x + _experts(p["moe"], h, arch=arch)


def arch_of(cfg, lora_alpha=16.0, lora_rank=8, first_expert=0,
            round_to=None):
    """What the equations need of a configuration file's dict (the
    published key names), hashable: it keys the jitted programs."""
    return tuple(sorted({
        "heads": cfg["num_attention_heads"],
        "qk_nope": cfg["qk_nope_head_dim"],
        "qk_rope": cfg["qk_rope_head_dim"], "v": cfg["v_head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "first_expert": first_expert, "eps": float(cfg["rms_norm_eps"]),
        "lora_scale": lora_alpha / lora_rank, "round_to": round_to}.items()))


@functools.lru_cache(maxsize=None)
def _programs(arch):
    arch = dict(arch)
    layer = functools.partial(_layer, arch=arch)

    @jax.jit
    def fwd(p, x):
        with jax.default_matmul_precision("highest"):
            return layer(p, x)

    @jax.jit
    def picks(p, x):
        with jax.default_matmul_precision("highest"):
            return _route(p["moe"], _rms(x, p["norm"]["scale"], arch["eps"]),
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
        return table.astype(jnp.float32)[tokens]

    def _logits(norm, head, x):
        return _rms(x, norm, arch["eps"]) @ head.astype(jnp.float32)

    @jax.jit
    def nll_and_grad(norm, head, x, targets):
        """Summed negative log-likelihood of one block of tokens, and
        its gradient in the block's hidden states."""
        def nll(x):
            logp = jax.nn.log_softmax(_logits(norm, head, x), -1)
            return -jnp.take_along_axis(logp, targets[..., None], -1).sum()

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(nll)(x)

    @jax.jit
    def logits(norm, head, x):
        with jax.default_matmul_precision("highest"):
            return _logits(norm, head, x)

    return types.SimpleNamespace(
        fwd=fwd, bwd=bwd, picks=picks, embed=embed,
        nll_and_grad=nll_and_grad, logits=logits)


def _mixers(params):
    return sum(name.startswith("layer_") for name in params)


def _forward(params, tokens, arch):
    """Inputs of every mixer and the last one's output."""
    prog = _programs(arch)
    xs = [prog.embed(params["embed"]["embedding"], tokens)]
    for i in range(_mixers(params)):
        xs.append(prog.fwd(params[f"layer_{i}"], xs[-1]))
    return xs


def _loss_and_grad(params, x, targets, arch):
    """Mean cross-entropy over every token and its gradient in `x`, a
    block of tokens at a time: the whole sequence's logits over a
    vocabulary of 154880 would be 5 GB."""
    prog = _programs(arch)
    seq = x.shape[1]
    block = LOSS_BLOCK if seq % LOSS_BLOCK == 0 else seq
    total, grads = 0.0, []
    for at in range(0, seq, block):
        nll, g = prog.nll_and_grad(
            params["final_norm"]["scale"], params["lm_head"]["kernel"],
            x[:, at:at + block], targets[:, at:at + block])
        total, grads = total + float(nll), grads + [g]
    return total / targets.size, jnp.concatenate(grads, 1) / targets.size


def loss_and_adapter_grad_norm(params, tokens, targets, arch):
    """Mean cross-entropy of `targets` after `tokens`, the global norm
    of its gradient over every ``lora_a``/``lora_b`` leaf, and for each
    expert layer the experts every token chose, ``{layer: (tokens,
    picks)}``: what the program's own choice is held against."""
    prog = _programs(arch)
    xs = _forward(params, tokens, arch)
    chosen = {i: prog.picks(params[f"layer_{i}"], xs[i])
              for i in range(_mixers(params)) if "moe" in params[f"layer_{i}"]}
    loss, g = _loss_and_grad(params, xs.pop(), targets, arch)
    square = jnp.zeros((), jnp.float32)
    for i in reversed(range(_mixers(params))):
        layer_square, g = prog.bwd(params[f"layer_{i}"], xs.pop(), g)
        square = square + layer_square
    return loss, float(jnp.sqrt(square)), chosen


def logits(params, tokens, arch):
    """Float32 logits of the full causal forward over `tokens`."""
    return _programs(arch).logits(
        params["final_norm"]["scale"], params["lm_head"]["kernel"],
        _forward(params, tokens, arch)[-1])
