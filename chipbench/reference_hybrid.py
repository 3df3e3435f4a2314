"""The plain reference of the patterned decoder (``nemotron_h``): in
straightforward ``jax.numpy`` and float32 at the highest matmul
precision. No kernel, no chunked scan, no sorted dispatch and no module
of the program: it takes the program's weight TREE (the names below)
and nothing else of it.

    embed/embedding, final_norm/scale, lm_head/kernel, and a layer
    layer_<i>/norm/scale with ONE of
      mamba/{in_proj,out_proj}/kernel (+ lora_a, lora_b), conv_kernel,
            conv_bias, dt_bias, A_log, D, norm_scale
      moe/{router,latent_in,latent_out,shared_up,shared_down}/kernel,
          router_bias, w_up, w_down
      attn/{q,k,v,o}_proj/kernel (+ lora_a, lora_b)

Equations (ISSUE 29, section 1; docs/hybrid.rst): every layer is
``x + mixer(RMSNorm(x))``.

- Mamba-2: ``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv1d_4(xBC)
  + b)`` causal and depthwise; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; a head's state ``h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``, run AS THAT RECURRENCE,
  a step at a time (``lax.scan``, a group of heads after another and
  checkpointed by blocks of steps, so that its backward fits); the
  gated norm by group; ``out_proj``.
- Latent experts: ``s = sigmoid(W_r u)``; the top k of ``s + b``;
  weights ``scale * s_k / (sum of the chosen s + 1e-20)``; ``v = W_in
  u``; every expert HELD on every token, ``W2 relu(W1 v)^2``, with its
  weight as a mask (zero where it was not chosen); ``W_out`` of the sum,
  plus the shared expert ``S2 relu(S1 u)^2``.
- Attention: grouped-query, causal, plain softmax, scale
  1/sqrt(head_dim), a query head at a time (8192^2 scores a head).

Departures, each as the program has it: no rotary embedding in the
attention layers (the configuration file's ``assumed`` says why); what
the experts held on OTHER chips would add is left out (the file's
``stands_for``); no multi-token-prediction module.

One layer's weights are upcast at a time, an expert's as it is used,
and the backward recomputes each layer from its saved input, so the
reference fits beside the live bf16 model on the chip.
"""

import functools
import types

import jax
import jax.numpy as jnp

SCAN_BLOCK = 128    # steps a checkpointed block of the recurrence


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _proj(p, x, lora_scale):
    y = x @ p["kernel"]
    if "lora_a" in p:
        y = y + lora_scale * ((x @ p["lora_a"]) @ p["lora_b"])
    return y


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _recurrence(x, dt, A, B, C, D):
    """x (b, s, g, r, p), dt (b, s, g, r), A and D (g, r), B and C
    (b, s, g, n): the r heads of a group share its B and C. One group
    at a time, so that a group's states are all that is held."""
    b, s, g, r, p = x.shape
    pad = -s % SCAN_BLOCK       # steps of dt = 0 leave the state alone

    def blocks(a):              # (b, s, ...) -> (blocks, steps, b, ...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(-1, SCAN_BLOCK, *a.shape[1:])

    @jax.checkpoint
    def group(of):
        x, dt, B, C, A, D = of  # x (b, s, r, p), B (b, s, n), A (r,)

        def step(state, at):
            x_t, dt_t, B_t, C_t = at
            state = (state * jnp.exp(dt_t * A)[..., None, None]
                     + (dt_t[..., None] * x_t)[..., None]
                     * B_t[:, None, None, :])
            y_t = (state * C_t[:, None, None, :]).sum(-1) + D[:, None] * x_t
            return state, y_t

        @jax.checkpoint
        def block(state, ats):
            return jax.lax.scan(step, state, ats)

        _, y = jax.lax.scan(block, jnp.zeros((b, r, p, B.shape[-1])),
                            tuple(map(blocks, (x, dt, B, C))))
        return jnp.moveaxis(y.reshape(-1, b, r, p), 0, 1)[:, :s]

    y = jax.lax.map(group, tuple(jnp.moveaxis(a, 2, 0) for a in (
        x, dt, B, C)) + (A, D))
    return jnp.moveaxis(y, 0, 2)                         # (b, s, g, r, p)


def _mamba(p, u, *, arch):
    b, s, _ = u.shape
    heads, hd = arch["ssm_heads"], arch["ssm_head_dim"]
    groups, n = arch["ssm_groups"], arch["ssm_state"]
    inner, bc = heads * hd, groups * n
    zxbcdt = _proj(p["in_proj"], u, arch["lora_scale"])
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:2 * inner + 2 * bc],
                  zxbcdt[..., 2 * inner + 2 * bc:])
    taps = p["conv_kernel"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_bias"] + sum(
        p["conv_kernel"][j] * padded[:, j:j + s] for j in range(taps)))
    by_group = (groups, heads // groups)
    y = _recurrence(
        xbc[..., :inner].reshape(b, s, *by_group, hd),
        jax.nn.softplus(dt + p["dt_bias"]).reshape(b, s, *by_group),
        -jnp.exp(p["A_log"]).reshape(by_group),
        xbc[..., inner:inner + bc].reshape(b, s, groups, n),
        xbc[..., inner + bc:].reshape(b, s, groups, n),
        p["D"].reshape(by_group))
    y = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + arch["eps"])
    return _proj(p["out_proj"], y.reshape(b, s, inner) * p["norm_scale"],
                 arch["lora_scale"])


def _route(p, u, *, arch):
    """(idx, weights) of every token over ALL the routed experts."""
    s = jax.nn.sigmoid(u @ p["router"]["kernel"].astype(jnp.float32))
    _, idx = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                           arch["top_k"])
    chosen = jnp.take_along_axis(s, idx, -1)
    return idx, arch["routed_scale"] * chosen / (
        chosen.sum(-1, keepdims=True) + 1e-20)


def _experts(p, u, *, arch):
    idx, weights = _route(p, u, arch=arch)
    small = _f32({k: p[k] for k in (
        "latent_in", "latent_out", "shared_up", "shared_down")})
    v = u @ small["latent_in"]["kernel"]

    @jax.checkpoint
    def one(v, weights, e, w_up, w_down):
        weight = jnp.where(idx == e, weights, 0.0).sum(-1, keepdims=True)
        return weight * (_relu2(v @ w_up.astype(jnp.float32))
                         @ w_down.astype(jnp.float32))

    def add(out, expert):
        return out + one(v, weights, *expert), None

    held = p["w_up"].shape[0]
    routed, _ = jax.lax.scan(add, jnp.zeros_like(v), (
        arch["first_expert"] + jnp.arange(held), p["w_up"], p["w_down"]))
    shared = _relu2(u @ small["shared_up"]["kernel"]) @ small[
        "shared_down"]["kernel"]
    return routed @ small["latent_out"]["kernel"] + shared


def _attention(p, u, *, arch):
    b, s, _ = u.shape
    heads, kv_heads = arch["n_heads"], arch["n_kv_heads"]
    q, k, v = (_proj(p[name], u, arch["lora_scale"]) for name in (
        "q_proj", "k_proj", "v_proj"))
    q = q.reshape(b, s, heads, -1)
    k, v = (jnp.repeat(a.reshape(b, s, kv_heads, -1), heads // kv_heads, 2)
            for a in (k, v))
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):
        q, k, v = qkv                                    # (b, s, d)
        scores = jnp.einsum("bqd,bkd->bqk", q, k) * q.shape[-1] ** -0.5
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1) @ v

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return _proj(p["o_proj"], jnp.moveaxis(o, 0, 2).reshape(b, s, -1),
                 arch["lora_scale"])


def _layer(p, x, *, arch):
    h = _rms(x, p["norm"]["scale"].astype(jnp.float32), arch["eps"])
    if "mamba" in p:
        return x + _mamba(_f32(p["mamba"]), h, arch=arch)
    if "moe" in p:
        return x + _experts(p["moe"], h, arch=arch)
    return x + _attention(_f32(p["attn"]), h, arch=arch)


def arch_of(cfg, lora_alpha=16.0, lora_rank=8, first_expert=0):
    """What the equations need of a configuration file's dict (the
    published key names), hashable: it keys the jitted programs."""
    return tuple(sorted({
        "pattern": cfg["hybrid_override_pattern"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "ssm_heads": cfg["mamba_num_heads"],
        "ssm_head_dim": cfg["mamba_head_dim"],
        "ssm_groups": cfg["n_groups"], "ssm_state": cfg["ssm_state_size"],
        "top_k": cfg["num_experts_per_tok"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "first_expert": first_expert, "eps": float(cfg["norm_eps"]),
        "lora_scale": lora_alpha / lora_rank}.items()))


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
            return _route(p["moe"], _rms(
                x, p["norm"]["scale"].astype(jnp.float32), arch["eps"]),
                arch=arch)[0]

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
    def logits(norm, head, x):
        with jax.default_matmul_precision("highest"):
            return _logits(norm, head, x)

    return types.SimpleNamespace(
        fwd=fwd, bwd=bwd, picks=picks, embed=embed,
        loss_and_grad=loss_and_grad, logits=logits)


def _forward(params, tokens, arch):
    """Inputs of every layer and the last layer's output."""
    prog = _programs(arch)
    xs = [prog.embed(params["embed"]["embedding"], tokens)]
    for i in range(len(dict(arch)["pattern"])):
        xs.append(prog.fwd(params[f"layer_{i}"], xs[-1]))
    return xs


def loss_and_adapter_grad_norm(params, tokens, targets, arch):
    """Mean cross-entropy of `targets` after `tokens`, the global norm
    of its gradient over every ``lora_a``/``lora_b`` leaf, and for each
    expert layer the experts every token chose, ``{layer: (tokens,
    picks)}``: what the program's own choice is held against."""
    prog = _programs(arch)
    xs = _forward(params, tokens, arch)
    chosen = {i: prog.picks(params[f"layer_{i}"], xs[i])
              for i, kind in enumerate(dict(arch)["pattern"]) if kind == "E"}
    loss, g = prog.loss_and_grad(params["final_norm"]["scale"],
                                 params["lm_head"]["kernel"], xs.pop(), targets)
    square = jnp.zeros((), jnp.float32)
    for i in reversed(range(len(dict(arch)["pattern"]))):
        layer_square, g = prog.bwd(params[f"layer_{i}"], xs.pop(), g)
        square = square + layer_square
    return float(loss), float(jnp.sqrt(square)), chosen


def logits(params, tokens, arch):
    """Float32 logits of the full causal forward over `tokens`."""
    return _programs(arch).logits(
        params["final_norm"]["scale"], params["lm_head"]["kernel"],
        _forward(params, tokens, arch)[-1])
