"""Mixture-of-Experts MLP with expert parallelism.

Beyond-reference capability (the reference scales data only, SURVEY.md
§2.3): a top-k routed expert MLP whose stacked expert weights shard
over an ``expert`` mesh axis, with TWO execution models behind the
same routing semantics:

- psum-combine (:func:`expert_parallel_moe`): every device computes
  its LOCAL experts for all replicated tokens; partial outputs psum.
  Simple, fine at small expert counts — but FLOPs scale with
  n_experts x all tokens.
- all_to_all dispatch (:func:`expert_parallel_moe_a2a`): tokens ride
  the ICI to their expert's shard in fixed-capacity buffers
  (Switch/Mixtral execution model) — FLOPs scale with capacity, the
  sparse-MoE point.
"""

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 128
    n_experts: int = 4
    top_k: int = 2
    dtype: Any = jnp.float32


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU expert MLP (stacked expert weights)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        # router stays replicated (tiny); experts are stacked on a
        # leading axis so an 'expert' sharding rule applies cleanly
        router = nn.Dense(cfg.n_experts, dtype=jnp.float32, name="router")
        w_gate = self.param(
            "w_gate", nn.initializers.lecun_normal(),
            (cfg.n_experts, cfg.d_model, cfg.d_ff),
        ).astype(cfg.dtype)
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(),
            (cfg.n_experts, cfg.d_model, cfg.d_ff),
        ).astype(cfg.dtype)
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(),
            (cfg.n_experts, cfg.d_ff, cfg.d_model),
        ).astype(cfg.dtype)

        probs = jax.nn.softmax(
            router(x.astype(jnp.float32)), axis=-1
        )                                          # (..., E)
        # Sown for the router-balance auxiliary loss: training reads it
        # via apply(..., mutable=["intermediates"]) + moe_aux_loss.
        self.sow("intermediates", "router_probs", probs)
        gates = gates_from_probs(probs, cfg.top_k).astype(cfg.dtype)
        return moe_apply(x, gates, w_gate, w_up, w_down)


def _topk_mask(probs, top_k):
    """Exact top-k membership mask via the indices top_k returns —
    a ``probs >= kth_value`` comparison would select more than
    ``top_k`` experts on probability ties (near-uniform init)."""
    _, idx = jax.lax.top_k(probs, top_k)           # (..., top_k)
    hot = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
    return hot.sum(axis=-2)                        # (..., E) in {0,1}


def gates_from_probs(probs, top_k):
    """Top-k gates from router probabilities, renormalized over the
    selected experts."""
    gated = probs * _topk_mask(probs, top_k)
    return gated / jnp.maximum(gated.sum(axis=-1, keepdims=True), 1e-9)


def moe_gates(logits, top_k):
    """Top-k softmax gates, renormalized over the selected experts."""
    return gates_from_probs(jax.nn.softmax(logits, axis=-1), top_k)


def load_balance_loss(probs, top_k):
    """Router load-balance auxiliary (switch-transformer form,
    generalized to top-k): ``E * sum_e f_e * P_e`` where ``f_e`` is the
    fraction of tokens routing to expert e (top-k membership) and
    ``P_e`` the mean router probability. Perfectly balanced routing
    gives ``top_k``; imbalance grows it toward ``E * top_k``."""
    n_experts = probs.shape[-1]
    flat = probs.reshape(-1, n_experts)
    chosen = _topk_mask(flat, top_k).astype(jnp.float32)
    f = chosen.mean(axis=0)
    p = flat.mean(axis=0)
    return n_experts * jnp.sum(f * p)


def moe_aux_loss(intermediates, top_k):
    """Sum :func:`load_balance_loss` over every sown ``router_probs``
    in an ``intermediates`` collection (one per MoE layer). Raises if
    none are present — a silent 0.0 would let the router train without
    balancing (the usual cause: forgetting
    ``mutable=["intermediates"]`` on apply)."""
    losses = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            intermediates)[0]:
        # sow stores a tuple per call; each element is one probs array
        if any(str(getattr(p, "key", "")) == "router_probs"
               for p in path):
            losses.append(load_balance_loss(leaf, top_k))
    if not losses:
        raise ValueError(
            "no router_probs found in intermediates — pass the "
            "'intermediates' collection from apply(..., "
            "mutable=['intermediates']) on an MoE model"
        )
    return jnp.stack(losses).sum()


def moe_apply(x, gates, w_gate, w_up, w_down, axis_name=None):
    """Gate-weighted expert combine. With ``axis_name`` (under
    shard_map), the stacked expert weights hold only LOCAL experts and
    partial outputs are psum'd over the expert axis."""
    with jax.named_scope("sparkdl.moe"):
        return _moe_apply(x, gates, w_gate, w_up, w_down, axis_name)


def _moe_apply(x, gates, w_gate, w_up, w_down, axis_name):
    h_gate = jnp.einsum("...d,edf->e...f", x, w_gate)
    h_up = jnp.einsum("...d,edf->e...f", x, w_up)
    h = nn.silu(h_gate) * h_up
    out_e = jnp.einsum("e...f,efd->e...d", h, w_down)   # (E_local, ..., d)
    combined = jnp.einsum("e...d,...e->...d", out_e, gates)
    if axis_name is not None:
        combined = jax.lax.psum(combined, axis_name)
    return combined


def _expert_axis_size(mesh, cfg, axis_name):
    """Shard count on ``axis_name`` + the divisibility guard shared by
    both expert-parallel execution models."""
    n_shards = dict(mesh.shape)[axis_name]
    if cfg.n_experts % n_shards:
        raise ValueError(
            f"n_experts={cfg.n_experts} not divisible by the "
            f"{axis_name} axis ({n_shards})"
        )
    return n_shards


def _expert_param_specs(axis_name):
    from jax.sharding import PartitionSpec as P

    return {
        "router": {"kernel": P(), "bias": P()},
        "w_gate": P(axis_name), "w_up": P(axis_name),
        "w_down": P(axis_name),
    }


def expert_parallel_moe(mesh, cfg, *, axis_name="expert"):
    """Bind an expert-parallel MoE forward to a mesh: returns
    ``f(params, x)`` on GLOBAL arrays where the stacked expert weights
    are sharded over ``axis_name`` and x / router are replicated.

    params: {"router": {"kernel", "bias"}, "w_gate", "w_up", "w_down"}
    (the tree produced by :class:`MoEMLP`.init).
    """
    from jax.sharding import PartitionSpec as P

    n_exp_shards = _expert_axis_size(mesh, cfg, axis_name)

    def local_fn(params, x):
        shard = jax.lax.axis_index(axis_name)
        logits = (
            x.astype(jnp.float32) @ params["router"]["kernel"]
            + params["router"]["bias"]
        )
        gates = moe_gates(logits, cfg.top_k).astype(x.dtype)
        # local expert slice of the gates
        e_local = cfg.n_experts // n_exp_shards
        g_local = jax.lax.dynamic_slice_in_dim(
            gates, shard * e_local, e_local, axis=-1
        )
        return moe_apply(
            x, g_local, params["w_gate"], params["w_up"],
            params["w_down"], axis_name=axis_name,
        )


    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(_expert_param_specs(axis_name), P()), out_specs=P(),
        check_vma=False,
    )


def expert_parallel_moe_a2a(mesh, cfg, *, axis_name="expert",
                            capacity_factor=1.25):
    """Dispatch-based expert parallelism: tokens ride ``all_to_all`` to
    the shard holding their expert (Switch/Mixtral execution model),
    so expert FLOPs scale with CAPACITY, not with
    n_experts x all-tokens like the psum-combine path
    (:func:`expert_parallel_moe`, which computes every local expert on
    every replicated token — fine at small expert counts, wasteful at
    scale).

    Per shard: route local tokens, pack each expert's selections into
    a fixed CAPACITY buffer (``C = ceil(tokens_local * top_k / E *
    capacity_factor)``; overflow tokens are DROPPED for that expert —
    their gate contribution becomes zero, the standard capacity
    trade), all_to_all the (E, C, d) buffers so each shard receives
    its own experts' tokens from every shard, run the expert SwiGLU on
    exactly those tokens, all_to_all back, and gate-combine.

    Returns ``f(params, x)`` on GLOBAL arrays: x sharded over tokens
    on ``axis_name`` (leading axis), expert weights sharded over
    ``axis_name``, router replicated — same param tree as
    :class:`MoEMLP`.
    """
    from jax.sharding import PartitionSpec as P

    n_shards = _expert_axis_size(mesh, cfg, axis_name)
    e_local = cfg.n_experts // n_shards

    def local_fn(params, x):
        d = x.shape[-1]
        lead = x.shape[:-1]
        xt = x.reshape(-1, d)                        # (T_local, d)
        T = xt.shape[0]
        E = cfg.n_experts
        # static per-expert buffer size: the a2a and expert matmuls
        # have fixed shapes regardless of where the router sends load
        C = max(1, int(np.ceil(T * cfg.top_k / E * capacity_factor)))
        logits = (
            xt.astype(jnp.float32) @ params["router"]["kernel"]
            + params["router"]["bias"]
        )
        gates = moe_gates(logits, cfg.top_k)            # (T, E) f32
        sel = (gates > 0).astype(jnp.int32)
        # per-expert slot index of each selected token, in token order
        pos = jnp.cumsum(sel, axis=0) - 1               # (T, E)
        keep = (sel == 1) & (pos < C)
        # dispatch tensor (T, E, C): one-hot slot per kept pair
        disp = (jax.nn.one_hot(pos, C, dtype=xt.dtype)
                * keep[..., None].astype(xt.dtype))
        buf = jnp.einsum("tec,td->ecd", disp, xt)       # (E, C, d)
        # exchange: shard s sends experts [s*e_local, (s+1)*e_local) of
        # every OTHER shard's buffer and receives its own experts'
        # buffers from all shards (split/concat on the expert axis)
        recv = jax.lax.all_to_all(
            buf, axis_name, split_axis=0, concat_axis=0, tiled=True,
        )                                               # (E, C, d) =
        # (n_shards * e_local, C, d) grouped [shard0's e_local, ...]
        tok_e = (recv.reshape(n_shards, e_local, C, d)
                 .transpose(1, 0, 2, 3)
                 .reshape(e_local, n_shards * C, d))
        h = jnp.einsum("ecd,edf->ecf", tok_e, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", tok_e, params["w_up"])
        out_e = jnp.einsum(
            "ecf,efd->ecd", nn.silu(h) * u, params["w_down"]
        )                                               # (e_local, SC, d)
        back = (out_e.reshape(e_local, n_shards, C, d)
                .transpose(1, 0, 2, 3)
                .reshape(E, C, d))
        out_buf = jax.lax.all_to_all(
            back, axis_name, split_axis=0, concat_axis=0, tiled=True,
        )                                               # (E, C, d) home
        combine = disp * gates.astype(xt.dtype)[..., None]
        y = jnp.einsum("tec,ecd->td", combine, out_buf)
        return y.reshape(*lead, d)


    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(_expert_param_specs(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False,
    )
