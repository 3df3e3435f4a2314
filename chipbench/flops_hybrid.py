"""Operations and bytes of the patterned decoder (``nemotron_h``) from
shapes: what the algorithm REQUIRES of the chip's share, never what a
program happens to execute. Every function takes the configuration
file's dict (the published key names) and plain numbers.

A multiply-add is two operations. Attention and the scan's products
within a chunk are counted causal: step i reads i + 1 steps. The routed
experts are counted by the (token, pick) pairs that land on THIS chip:
in expectation ``num_experts_per_tok * held / routed`` a token, or the
rows a run counted.
"""

from chipbench import flops

BF16, F32 = 2, 4  # bytes


def routed_experts(cfg):
    """The router's width: every routed expert of the deployment."""
    held = cfg["n_routed_experts"]
    return cfg.get("reduced", {}).get("n_routed_experts", {}).get("from", held)


def _ssm(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return heads, p, cfg["n_groups"], cfg["ssm_state_size"]


def mamba_matmul_params(cfg):
    """in_proj (z, x, B, C, dt) and out_proj of ONE state-space layer."""
    d = cfg["hidden_size"]
    heads, p, groups, n = _ssm(cfg)
    inner = heads * p
    return d * (2 * inner + 2 * groups * n + heads) + inner * d


def attention_matmul_params(cfg):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def expert_params(cfg):
    """ONE routed expert: two matrices in the latent width."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def moe_matmul_params(cfg, picks_here=None):
    """Weights one token is multiplied with in ONE expert layer on this
    chip: the router over all routed experts, the latent projections,
    the shared expert, and the experts of `picks_here` of its picks
    (default: the expected share)."""
    d = cfg["hidden_size"]
    if picks_here is None:
        picks_here = (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                      / routed_experts(cfg))
    return (d * routed_experts(cfg) + 2 * d * cfg["moe_latent_size"]
            + 2 * d * cfg["moe_shared_expert_intermediate_size"]
            * cfg["n_shared_experts"] + picks_here * expert_params(cfg))


def layer_params(cfg, kind):
    """Weights ONE layer of `kind` (a letter of the pattern) holds."""
    d = cfg["hidden_size"]
    heads, p, groups, n = _ssm(cfg)
    if kind == "M":
        channels = heads * p + 2 * groups * n
        return (mamba_matmul_params(cfg) + (cfg["conv_kernel"] + 1) * channels
                + 3 * heads + heads * p + d)
    if kind == "E":
        return (moe_matmul_params(cfg, picks_here=cfg["n_routed_experts"])
                + routed_experts(cfg) + d)
    return attention_matmul_params(cfg) + d


def model_params(cfg):
    """Weights of the share as cut: layers, embedding, untied head,
    final norm."""
    d = cfg["hidden_size"]
    return (sum(layer_params(cfg, kind)
                for kind in cfg["hybrid_override_pattern"])
            + 2 * cfg["vocab_size"] * d + d)


def scan_flops_per_token(cfg):
    """The state-space scan in its chunked form at the published chunk,
    forward, per token and layer: within a chunk ``C B^T`` (a group's,
    shared by its heads) and its product with ``x``, both causal; the
    chunk's state ``x^T B``; and ``C`` times the state carried in."""
    heads, p, groups, n = _ssm(cfg)
    steps = (cfg["chunk_size"] + 1) / 2
    return (2 * steps * n * groups + heads * (2 * steps * p + 4 * p * n))


def ssd_scan_cost(cfg, batch, seq, *, backward):
    """(operations, bytes) of the scan of ONE layer over (batch, seq).
    Backward: the gradient of every product in both operands, twice the
    forward. Bytes: x and y once each in bf16, B and C, dt in float32;
    backward re-reads them with dy and writes dx, dB, dC, ddt."""
    heads, p, groups, n = _ssm(cfg)
    tokens = batch * seq
    ops = tokens * scan_flops_per_token(cfg) * (2 if backward else 1)
    xy, bc, dt = heads * p * BF16, groups * n * BF16, heads * F32
    per_token = 3 * xy + 4 * bc + 2 * dt if backward else 2 * xy + 2 * bc + dt
    return ops, tokens * per_token


def grouped_matmul_cost(cfg, rows):
    """(operations, bytes) of the two grouped products of ONE expert
    layer over `rows` (token, pick) pairs in ONE pass: the forward, or
    the backward a frozen base needs (the gradient in the rows only,
    which is as much again). Bytes: each row in and out of both
    products in bf16, and every held expert's two matrices once."""
    latent, d_ff = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    ops = rows * 2 * expert_params(cfg)
    nbytes = (rows * 2 * (latent + d_ff) * BF16
              + cfg["n_routed_experts"] * expert_params(cfg) * BF16)
    return ops, nbytes


def lora_adapter_params(cfg, rank, targets):
    """Adapter weights of ONE layer of each kind, ``{letter: count}``:
    A (in, r) and B (r, out) on each target projection the layer has."""
    d = cfg["hidden_size"]
    heads, p, groups, n = _ssm(cfg)
    inner = heads * p
    q, kv = (cfg[k] * cfg["head_dim"] for k in (
        "num_attention_heads", "num_key_value_heads"))
    shapes = {"M": {"in_proj": (d, 2 * inner + 2 * groups * n + heads),
                    "out_proj": (inner, d)},
              "*": {"q_proj": (d, q), "k_proj": (d, kv), "v_proj": (d, kv),
                    "o_proj": (q, d)},
              "E": {}}
    return {kind: sum(rank * sum(shape) for name, shape in has.items()
                      if name in targets) for kind, has in shapes.items()}


def lora_train_flops_per_token(cfg, seq, *, rank, targets):
    """Required operations per token of one LoRA step on the frozen
    share: forward and the backward's ACTIVATION gradients through
    every frozen matrix (2 + 2 a weight), the adapters' forward and both
    gradients (2 + 2 + 2), attention and the scan forward and backward,
    the convolution, and the frozen head forward and back.
    Recomputation (remat) is not required work and is not counted."""
    pattern = cfg["hybrid_override_pattern"]
    count = {kind: pattern.count(kind) for kind in "ME*"}
    heads, p, groups, n = _ssm(cfg)
    base = (count["M"] * mamba_matmul_params(cfg)
            + count["E"] * moe_matmul_params(cfg)
            + count["*"] * attention_matmul_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])
    adapters = sum(count[kind] * a for kind, a in
                   lora_adapter_params(cfg, rank, targets).items())
    conv = count["M"] * cfg["conv_kernel"] * (heads * p + 2 * groups * n)
    return (4 * (base + conv) + 6 * adapters
            + count["*"] * flops.attention_flops_per_token(
                cfg, seq, backward=True)
            + count["M"] * 3 * scan_flops_per_token(cfg))
