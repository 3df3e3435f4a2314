"""The plain reference: the decoder the configurations publish, in
straightforward ``jax.numpy`` and float32 at the highest matmul
precision. No kernel, no cache, no batching trick and no module of the
program: it takes the program's weight TREE (the names below) and
nothing else of it.

    embed/embedding, layer_<i>/{attn_norm,mlp_norm}/scale,
    layer_<i>/attn/{q,k,v,o}_proj/kernel (+ lora_a, lora_b),
    layer_<i>/mlp/{gate,up,down}_proj/kernel   or
    layer_<i>/moe_mlp/{router/{kernel,bias}, w_gate, w_up, w_down},
    final_norm/scale, lm_head/kernel

Equations: RMS norm, rotary embedding in the split-half layout
(``rotate_half``, as the published Mistral code has it), grouped-query
causal attention, SwiGLU, and for routed experts softmax over all
experts, top-k, renormalised over the chosen k (Mixtral's rule).
Departure noted: the program's router carries a bias (zero at init);
the reference adds it where the tree has one.

One layer's weights are upcast at a time, and the backward recomputes
each layer from its saved input, so the reference fits beside the live
bf16 model on the chip.
"""

import functools
import types

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (batch, seq, heads, head_dim), positions 0..seq-1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _proj(p, x, lora_scale):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + lora_scale * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def _attention(p, x, *, n_heads, n_kv_heads, theta, lora_scale):
    b, s, _ = x.shape
    q = _proj(p["q_proj"], x, lora_scale).reshape(b, s, n_heads, -1)
    k = _proj(p["k_proj"], x, lora_scale).reshape(b, s, n_kv_heads, -1)
    v = _proj(p["v_proj"], x, lora_scale).reshape(b, s, n_kv_heads, -1)
    q, k = _rope(q, theta), _rope(k, theta)
    group = n_heads // n_kv_heads
    q = q.reshape(b, s, n_kv_heads, group, -1)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, -1)
    return _proj(p["o_proj"], o, lora_scale)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _experts(p, x, top_k):
    """Every expert on every token, weighted by a gate that is zero off
    the chosen k: the plain way to write top-k routing, if not the
    cheap one. An expert's weights are upcast as it is used."""
    router = _f32(p["router"])
    logits = x @ router["kernel"]
    if "bias" in router:
        logits = logits + router["bias"]
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(probs.shape[-1]):
        weight = jnp.where(idx == e, top, 0.0).sum(-1, keepdims=True)
        out = out + weight * _swiglu(
            x, *(p[w][e].astype(jnp.float32)
                 for w in ("w_gate", "w_up", "w_down")))
    return out


def _layer(p, x, *, arch):
    p = {k: v if k == "moe_mlp" else _f32(v) for k, v in p.items()}
    x = x + _attention(
        p["attn"], _rms(x, p["attn_norm"]["scale"], arch["eps"]),
        n_heads=arch["n_heads"], n_kv_heads=arch["n_kv_heads"],
        theta=arch["theta"], lora_scale=arch["lora_scale"])
    h = _rms(x, p["mlp_norm"]["scale"], arch["eps"])
    if "moe_mlp" in p:
        return x + _experts(p["moe_mlp"], h, arch["top_k"])
    m = p["mlp"]
    return x + _swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                       m["down_proj"]["kernel"])


def arch_of(cfg, lora_alpha=16.0, lora_rank=8):
    """What the equations need of a configuration file's dict, hashable
    (it keys the jitted layer programs)."""
    return tuple(sorted({
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "top_k": cfg.get("num_experts_per_tok", 0),
        "lora_scale": lora_alpha / lora_rank,
        "n_layers": cfg["num_hidden_layers"]}.items()))


@functools.lru_cache(maxsize=None)
def _programs(arch):
    arch = dict(arch)
    layer = functools.partial(_layer, arch=arch)

    @jax.jit
    def fwd(p, x):
        with jax.default_matmul_precision("highest"):
            return layer(p, x)

    @jax.jit
    def bwd(p, x, g):
        """(sum of squares of the adapter gradients, dx) of one layer:
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
        x = _rms(x, norm.astype(jnp.float32), arch["eps"])
        return x @ head.astype(jnp.float32)

    @jax.jit
    def loss_and_grad(norm, head, x, targets):
        def loss(x):
            logp = jax.nn.log_softmax(_logits(norm, head, x), -1)
            return -jnp.take_along_axis(logp, targets[..., None], -1).mean()

        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(loss)(x)

    @jax.jit
    def logits_at(norm, head, x, rows, cols):
        with jax.default_matmul_precision("highest"):
            return _logits(norm, head, x[rows, cols])

    return types.SimpleNamespace(
        fwd=fwd, bwd=bwd, embed=embed, loss_and_grad=loss_and_grad,
        logits_at=logits_at)


def _forward(params, tokens, arch):
    """Inputs of every layer and the last layer's output."""
    prog = _programs(arch)
    xs = [prog.embed(params["embed"]["embedding"], tokens)]
    for i in range(dict(arch)["n_layers"]):
        xs.append(prog.fwd(params[f"layer_{i}"], xs[-1]))
    return xs


def loss_and_adapter_grad_norm(params, tokens, targets, arch):
    """Mean cross-entropy of `targets` after `tokens`, and the global
    norm of its gradient over every ``lora_a``/``lora_b`` leaf."""
    prog = _programs(arch)
    xs = _forward(params, tokens, arch)
    loss, g = prog.loss_and_grad(params["final_norm"]["scale"],
                            params["lm_head"]["kernel"], xs.pop(), targets)
    square = jnp.zeros((), jnp.float32)
    for i in reversed(range(dict(arch)["n_layers"])):
        layer_square, g = prog.bwd(params[f"layer_{i}"], xs.pop(), g)
        square = square + layer_square
    return float(loss), float(jnp.sqrt(square))


def logits_at(params, tokens, rows, cols, arch):
    """Float32 logits after positions (rows[i], cols[i]) of the full
    causal forward over `tokens` (batch, seq)."""
    x = _forward(params, tokens, arch)[-1]
    return _programs(arch).logits_at(params["final_norm"]["scale"], params["lm_head"]["kernel"],
                x, jnp.asarray(rows), jnp.asarray(cols))
