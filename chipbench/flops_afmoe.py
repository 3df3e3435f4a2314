"""Operations and bytes of a decoder that mixes window and full
attention layers over gated routed experts (``afmoe``) from shapes:
what the algorithm REQUIRES, never what a program happens to execute.
Every function takes the configuration file's dict (the published key
names) and plain numbers.

A multiply-add is two operations. A query at position i attends to
``i + 1`` keys in a ``full_attention`` layer and to ``min(i + 1,
sliding_window)`` in a ``sliding_attention`` layer: a kernel that masks
the window without skipping what lies outside it does more than is
counted here and reads UNDER its roofline share, never over. Every
routed expert is held, so a token's ``num_experts_per_tok`` picks all
land here.
"""

from chipbench import flops
from chipbench.common import peaks_for

BF16 = 2  # bytes


def attention_projections(cfg):
    """``{name: (in, out)}`` of ONE attention mixer's five projections:
    heads x head size is not the hidden size here."""
    d, head = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * head, cfg["num_key_value_heads"] * head
    return {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
            "gate_proj": (d, q), "o_proj": (q, d)}


def attention_matmul_params(cfg):
    return sum(i * o for i, o in attention_projections(cfg).values())


def dense_mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """ONE routed expert: gate, up and down on the full width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_matmul_params(cfg):
    """Weights one token is multiplied with in ONE expert layer: the
    router, the shared experts and the experts of its picks."""
    return (cfg["hidden_size"] * cfg["num_experts"]
            + (cfg["num_shared_experts"] + cfg["num_experts_per_tok"])
            * expert_params(cfg))


def layers(cfg):
    """(dense, expert) published layers of the model as cut."""
    dense = cfg["num_dense_layers"]
    return dense, cfg["num_hidden_layers"] - dense


def attention_layers(cfg):
    """(window, full) attention mixers of the model as cut."""
    window = sum(t == "sliding_attention" for t in cfg["layer_types"])
    return window, len(cfg["layer_types"]) - window


def matrix_params(cfg):
    """Weights of the model's matrices as cut: every layer's five
    attention projections, the dense MLPs, the expert layers with the
    router, every routed expert and the shared ones, embedding and
    untied head."""
    dense, expert = layers(cfg)
    moe = (cfg["hidden_size"] * cfg["num_experts"]
           + (cfg["num_shared_experts"] + cfg["num_experts"])
           * expert_params(cfg))
    return ((dense + expert) * attention_matmul_params(cfg)
            + dense * dense_mlp_params(cfg) + expert * moe
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def model_params(cfg):
    """Every weight held: the matrices, four norms a layer, the two
    norms a head of each attention mixer, the routers' biases and the
    final norm."""
    dense, expert = layers(cfg)
    d = cfg["hidden_size"]
    return (matrix_params(cfg)
            + (dense + expert) * (4 * d + 2 * cfg["head_dim"])
            + expert * cfg["num_experts"] + d)


def visible_pairs(seq, window=None):
    """(query, key) pairs with ``0 <= i - j`` and, with a `window`,
    ``i - j < window`` over a sequence of `seq`, a head."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops_per_token(cfg, seq, *, window, backward):
    """Attention over a sequence of `seq`, per token and layer: QK^T
    and PV over the visible pairs forward, and dV, dP, dQ, dK backward
    (each product once more, twice)."""
    per_pair = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    forward = per_pair * visible_pairs(seq, window) / seq
    return forward * (3 if backward else 1)


def _attention_cost(cfg, batch, seq, window, backward):
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ops = batch * seq * attention_flops_per_token(
        cfg, seq, window=window, backward=False) * (2 if backward else 1)
    # q and o at the query heads, k and v at their own (a group of query
    # heads reads one key/value head); the backward reads them again
    # with do and writes dq, dk, dv
    tensors = batch * seq * cfg["head_dim"] * 2 * (heads + kv_heads) * BF16
    return ops, tensors * (2 if backward else 1)


def window_attention_cost(cfg, batch, seq, *, backward):
    """(operations, bytes) of ONE ``sliding_attention`` layer's
    attention over (batch, seq): the pairs inside the window only."""
    return _attention_cost(cfg, batch, seq, cfg["sliding_window"], backward)


def flash_attention_cost(cfg, batch, seq, *, backward):
    """(operations, bytes) of ONE ``full_attention`` layer's causal
    attention over (batch, seq)."""
    return _attention_cost(cfg, batch, seq, None, backward)


def attention_step_cost(cfg, batch, seq):
    """``{"window": (operations, bytes), "full": ...}`` of ONE step's
    required attention, forward once and backward once, over all the
    mixers of each kind (the remat's second forward is not required
    work)."""
    window, full = attention_layers(cfg)
    costs = {}
    for kind, cost, mixers in (("window", window_attention_cost, window),
                               ("full", flash_attention_cost, full)):
        passes = [cost(cfg, batch, seq, backward=b) for b in (False, True)]
        costs[kind] = tuple(mixers * sum(p) for p in zip(*passes))
    return costs


def attention_roofline_seconds(spec, device_kind):
    """``{"window": seconds, "full": seconds}``: the least time the
    chip could take for one step's required attention of each kind
    (`spec` is ``run.load_cell``'s)."""
    job, peaks = spec["traffic"], peaks_for(spec["peaks"], device_kind)
    return {kind: flops.roofline_seconds(*cost, peaks)[0]
            for kind, cost in attention_step_cost(
                spec["config"], job["batch"], job["seq"]).items()}


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
              + cfg["num_experts"] * expert_params(cfg) * BF16)
    return ops, nbytes


def lora_adapter_params(cfg, rank, targets):
    """Adapter weights of ONE attention mixer: A (in, r) and B (r, out)
    on each target projection."""
    return sum(rank * (i + o) for name, (i, o) in
               attention_projections(cfg).items() if name in targets)


def lora_train_flops_per_token(cfg, seq, *, rank, targets):
    """Required operations per token of one LoRA step on the frozen
    base: forward and the backward's ACTIVATION gradients through every
    frozen matrix (2 + 2 a weight), the adapters' forward and both
    gradients (2 + 2 + 2), attention forward and backward over the
    pairs each layer kind sees, and the frozen head forward and back.
    Recomputation (remat) is not required work and is not counted."""
    dense, expert = layers(cfg)
    window, full = attention_layers(cfg)
    base = ((dense + expert) * attention_matmul_params(cfg)
            + dense * dense_mlp_params(cfg) + expert * moe_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])
    adapters = (dense + expert) * lora_adapter_params(cfg, rank, targets)
    attention = (
        window * attention_flops_per_token(
            cfg, seq, window=cfg["sliding_window"], backward=True)
        + full * attention_flops_per_token(
            cfg, seq, window=None, backward=True))
    return 4 * base + 6 * adapters + attention
