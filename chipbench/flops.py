"""Operations and bytes from shapes: what the algorithm REQUIRES, never
what a program happens to execute. Every function takes a configuration
file's dict (the published key names) and plain numbers.

A multiply-add is two operations. Attention is counted causal: a query
at position i attends to i + 1 keys, (S + 1) / 2 on average.
"""

BF16 = 2  # bytes


def _sizes(cfg):
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or d // heads
    return d, heads, cfg["num_key_value_heads"], head_dim


def layer_matmul_params(cfg):
    """Weights one token is multiplied with in ONE decoder layer:
    q, k, v, o and the feed-forward. With routed experts: the router
    and the `num_experts_per_tok` experts a token is sent to, not all
    that the layer holds."""
    d, heads, kv_heads, head_dim = _sizes(cfg)
    attn = d * heads * head_dim * 2 + d * kv_heads * head_dim * 2
    ffn = 3 * d * cfg["intermediate_size"]
    experts = cfg.get("num_local_experts", 0)
    if experts:
        return attn + d * experts + cfg["num_experts_per_tok"] * ffn
    return attn + ffn


def layer_params(cfg):
    """Weights ONE decoder layer holds (every expert, the two norms)."""
    d, heads, kv_heads, head_dim = _sizes(cfg)
    attn = d * heads * head_dim * 2 + d * kv_heads * head_dim * 2
    ffn = 3 * d * cfg["intermediate_size"]
    experts = cfg.get("num_local_experts", 0)
    ffn = experts * ffn + d * experts + experts if experts else ffn
    return attn + ffn + 2 * d


def model_params(cfg):
    """Weights of the model as cut: layers, embedding, untied head,
    final norm."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * d + d)


def attention_flops_per_token(cfg, seq, *, backward):
    """Causal self-attention over a sequence of `seq`, per token and
    layer: QK^T and PV forward (2 matmuls), and dV, dP, dQ, dK backward
    (4)."""
    _, heads, _, head_dim = _sizes(cfg)
    per_matmul = 2 * heads * head_dim * (seq + 1) / 2
    return per_matmul * (6 if backward else 2)


def lora_adapter_params(cfg, rank, targets):
    """Adapter weights of ONE layer: A (in, r) and B (r, out) on each
    target projection."""
    d, heads, kv_heads, head_dim = _sizes(cfg)
    out = {"q_proj": heads * head_dim, "k_proj": kv_heads * head_dim,
           "v_proj": kv_heads * head_dim, "o_proj": d}
    return sum(rank * ((heads * head_dim if t == "o_proj" else d) + out[t])
               for t in targets)


def lora_train_flops_per_token(cfg, seq, *, rank, targets):
    """Required operations per token of one LoRA step on a frozen base:
    forward, then the backward's ACTIVATION gradients through every
    frozen matrix (2 + 2 per weight: no weight gradient of the base is
    needed), forward, activation- and weight-gradient of the adapters
    (2 + 2 + 2), attention forward and backward, and the frozen head
    forward and back. Recomputation (remat) is not required work and is
    not counted. The first layer's q/k/v need no input gradient; that
    0.1 % is counted all the same."""
    layers = cfg["num_hidden_layers"]
    base = layers * layer_matmul_params(cfg) + (
        cfg["vocab_size"] * cfg["hidden_size"])
    adapters = layers * lora_adapter_params(cfg, rank, targets)
    attn = layers * attention_flops_per_token(cfg, seq, backward=True)
    return 4 * base + 6 * adapters + attn


def forward_flops_per_token(cfg, context):
    """Required operations of one forward token that attends to
    `context` keys (decode: the row's cache; no causal halving)."""
    _, heads, _, head_dim = _sizes(cfg)
    layers = cfg["num_hidden_layers"]
    return (2 * (layers * layer_matmul_params(cfg)
                 + cfg["vocab_size"] * cfg["hidden_size"])
            + layers * 4 * heads * head_dim * context)


def flash_attention_cost(cfg, batch, seq, *, backward):
    """(operations, bytes) of causal flash attention over (batch, seq)
    in ONE layer. Bytes: q, k, v and o once each in bf16 (k and v at
    the query-head count: the program repeats them for grouped-query
    attention before the kernel), and for the backward also do, dq, dk,
    dv and the re-read of q, k, v, o."""
    _, heads, _, head_dim = _sizes(cfg)
    ops = batch * seq * attention_flops_per_token(
        cfg, seq, backward=False) * (2 if backward else 1)
    tensor = batch * seq * heads * head_dim * BF16
    return ops, tensor * (8 if backward else 4)


def paged_decode_cost(cfg, context_lens, page_size):
    """(operations, bytes) of ONE decode step's paged attention in ONE
    layer over rows that hold `context_lens` keys: bytes are the K and
    V pages each row really holds (whole pages), in bf16."""
    _, heads, kv_heads, head_dim = _sizes(cfg)
    ops = sum(4 * heads * head_dim * n for n in context_lens)
    pages = sum(-(-n // page_size) for n in context_lens)
    return ops, pages * page_size * kv_heads * head_dim * 2 * BF16


def roofline_seconds(ops, nbytes, peaks):
    """The least time the chip could take and which bound sets it."""
    by_compute = ops / peaks["bf16_flops_per_s"]
    by_memory = nbytes / peaks["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory else (
        by_memory, "memory")
