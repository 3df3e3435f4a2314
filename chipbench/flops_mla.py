"""Operations and bytes of a latent-attention expert decoder
(``glm4_moe_lite``) from shapes: what the algorithm REQUIRES, never what
a program happens to execute. Every function takes the configuration
file's dict (the published key names) and plain numbers.

A multiply-add is two operations. Attention is counted causal, by
``flops.attention_flops_per_token``'s convention (a query at position i
attends to i + 1 keys), in the EXPANDED form: scores at ``qk_nope +
qk_rope`` a head, values at ``v_head_dim``. Every routed expert is
held, so a token's ``num_experts_per_tok`` picks all land here.
"""

BF16 = 2  # bytes


def _heads(cfg):
    return (cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def mla_projections(cfg):
    """``{name: (in, out)}`` of ONE latent-attention mixer's five
    projections."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg[k] for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"q_a_proj": (d, q_rank),
            "q_b_proj": (q_rank, heads * (nope + rope)),
            "kv_a_proj_with_mqa": (d, kv_rank + rope),
            "kv_b_proj": (kv_rank, heads * (nope + v)),
            "o_proj": (heads * v, d)}


def mla_matmul_params(cfg):
    return sum(i * o for i, o in mla_projections(cfg).values())


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """ONE routed expert: gate, up and down on the full width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_matmul_params(cfg):
    """Weights one token is multiplied with in ONE expert layer: the
    router, the shared experts and the experts of its picks."""
    return (cfg["hidden_size"] * cfg["n_routed_experts"]
            + (cfg["n_shared_experts"] + cfg["num_experts_per_tok"])
            * expert_params(cfg))


def layers(cfg):
    """(dense, expert) published layers of the model as cut."""
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def model_params(cfg):
    """Weights of the model as cut: every layer's attention with its
    two latent norms and the layer's two norms, the dense MLPs, the
    expert layers with every expert and the selection bias, embedding,
    untied head, final norm."""
    d = cfg["hidden_size"]
    dense, expert = layers(cfg)
    attention = (mla_matmul_params(cfg) + cfg["q_lora_rank"]
                 + cfg["kv_lora_rank"] + 2 * d)
    routed = cfg["n_routed_experts"]
    moe = (d * routed + routed
           + (cfg["n_shared_experts"] + routed) * expert_params(cfg))
    return ((dense + expert) * attention + dense * dense_mlp_params(cfg)
            + expert * moe + 2 * cfg["vocab_size"] * d + d)


def attention_flops_per_token(cfg, seq, *, backward):
    """Causal attention over a sequence of `seq`, per token and layer:
    QK^T at the scores' head size and PV at the values' forward, and
    dV, dP, dQ, dK backward (each product once more, twice)."""
    heads, qk, v = _heads(cfg)
    forward = 2 * heads * (qk + v) * (seq + 1) / 2
    return forward * (3 if backward else 1)


def flash_attention_cost(cfg, batch, seq, *, backward):
    """(operations, bytes) of causal attention over (batch, seq) in ONE
    layer, expanded form. Bytes: q, k, v and o once each in bf16 at
    their head sizes, every head its own keys and values; the backward
    also reads do and writes dq, dk, dv and re-reads q, k, v, o."""
    heads, qk, v = _heads(cfg)
    ops = batch * seq * attention_flops_per_token(
        cfg, seq, backward=False) * (2 if backward else 1)
    tensors = batch * seq * heads * 2 * (qk + v) * BF16
    return ops, tensors * (2 if backward else 1)


def grouped_matmul_cost(cfg, rows):
    """(operations, bytes) of the two grouped products of ONE expert
    layer over `rows` (token, pick) pairs in ONE pass: the forward, or
    the backward a frozen base needs (the gradient in the rows only,
    which is as much again). Bytes: each row in and out of both
    products in bf16 (d -> gate | up, their product -> d), and every
    expert's three matrices once."""
    d, d_ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = rows * 2 * expert_params(cfg)
    nbytes = (rows * (d + 2 * d_ff + d_ff + d) * BF16
              + cfg["n_routed_experts"] * expert_params(cfg) * BF16)
    return ops, nbytes


def lora_adapter_params(cfg, rank, targets):
    """Adapter weights of ONE latent-attention mixer: A (in, r) and
    B (r, out) on each target projection."""
    return sum(rank * (i + o) for name, (i, o) in
               mla_projections(cfg).items() if name in targets)


def lora_train_flops_per_token(cfg, seq, *, rank, targets):
    """Required operations per token of one LoRA step on the frozen
    base: forward and the backward's ACTIVATION gradients through every
    frozen matrix (2 + 2 a weight), the adapters' forward and both
    gradients (2 + 2 + 2), attention forward and backward, and the
    frozen head forward and back. Recomputation (remat) is not required
    work and is not counted."""
    dense, expert = layers(cfg)
    base = ((dense + expert) * mla_matmul_params(cfg)
            + dense * dense_mlp_params(cfg) + expert * moe_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])
    adapters = (dense + expert) * lora_adapter_params(cfg, rank, targets)
    return (4 * base + 6 * adapters + (dense + expert)
            * attention_flops_per_token(cfg, seq, backward=True))
