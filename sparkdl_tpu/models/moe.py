"""Mixture-of-Experts layers.

Beyond-reference capability (the reference scales data only, SURVEY.md
§2.3). THREE execution models:

- psum-combine (:func:`expert_parallel_moe`): a top-k routed SwiGLU
  expert MLP (:class:`MoEMLP`) whose stacked expert weights shard over
  an ``expert`` mesh axis; every device computes its LOCAL experts for
  all replicated tokens and partial outputs psum. Simple, fine at
  small expert counts — but FLOPs scale with n_experts x all tokens.
  :class:`MoEMLP` alone (``LlamaConfig.n_experts``) is this on one
  device: still dense, every expert on every token.
- all_to_all dispatch (:func:`expert_parallel_moe_a2a`): tokens ride
  the ICI to their expert's shard in fixed-capacity buffers
  (Switch/Mixtral execution model) — FLOPs scale with capacity, the
  sparse-MoE point; a token past an expert's capacity is dropped.
- sorted dispatch on ONE chip's share (:func:`sorted_experts`): the
  layer is told which experts of the deployment's it holds, routes
  over all of them, sorts the (token, pick) pairs by expert and runs
  two grouped products over the pairs that land here. No token is
  dropped whatever the imbalance, and work goes with the pairs, not
  with the experts held: the products and, on a TPU, the rows' way out
  and back (:mod:`sparkdl_tpu.ops.pallas.moe_rows`) visit only the
  pairs held. It serves TWO expert forms (``FORMS``): ``relu2``
  experts in a latent width (:class:`LatentMoE`,
  ``nemotron3super-lora-train``) and gated ``silu(gate) * up`` experts
  on the full width, gate and up fused into the first product
  (:class:`GatedMoE`, ``glm47flash-lora-train``). These are the ones
  the on-chip benchmark measures (PERF.md); the exchange that would
  bring the other chips' tokens here is not built.
"""

import dataclasses
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from sparkdl_tpu import observe
from sparkdl_tpu.models.llama import _dense
from sparkdl_tpu.ops._dispatch import use_pallas as _use_pallas
from sparkdl_tpu.ops.grouped_matmul import grouped_matmul


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


def route_sigmoid(logits, bias, top_k, scale=1.0):
    """Sigmoid routing with a selection bias (DeepSeek-V3's rule, one
    group): scores ``s = sigmoid(logits)`` in float32; the ``top_k``
    experts are chosen by ``s + bias``, and weighted by ``s`` alone,
    normalised over the chosen and scaled. Returns ``(idx, weights)``,
    both (..., top_k)."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


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


# -- one chip's share of a layer's experts: sorted dispatch -----------------
#
# The (token, pick) pairs are sorted by expert, the pairs of experts held
# elsewhere last. `order[r]` is the pair that lies at row r of the sorted
# buffer; the first `n = counts.sum()` rows are the pairs that land here,
# and only they are numbers: the grouped products compute no row past `n`,
# forward or backward.
#
# Rows move between tokens and the buffer in one of two ways, decided by
# what the code can observe (:func:`dispatch_path`):
#
# - "pallas": the kernels of `ops/pallas/moe_rows.py`. A take writes
#   `rows[r] = v[token of r]` for r < n and an add sums `w[r] * y[r]` into
#   the token of r for r < n; each is the other's gradient. Rows past `n`
#   are neither read nor written: the work follows `n`, not tokens x picks.
# - "jnp": plain gathers over all tokens x picks rows in both directions
#   (`inverse` undoes `order`), `here` masking what comes back from a row
#   past `n` (a select, so that whatever lies there does no harm). Gathers,
#   because a scatter-add of 180,000 rows is what JAX would otherwise
#   derive, and the TPU runs that a row at a time.


def dispatch_path(tokens, latent, interpret=None):
    """Which way the rows of a layer of (tokens, latent) move: "pallas"
    on a TPU for the shapes the kernels take (interpreted, for tests,
    whatever the backend), else "jnp"."""
    from sparkdl_tpu.ops.pallas.moe_rows import takes_shape

    if interpret is None and not _use_pallas():
        return "jnp"
    taken = takes_shape(tokens, latent, tiled=not interpret)
    return "pallas" if taken else "jnp"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take(v, tok, n, static):
    """(tokens, d) -> (tokens x picks, d) sorted by expert, the first
    `n` rows of it; `tok[r]` is row r's token. `static`: ``(tile,
    interpret)`` of the kernels."""
    from sparkdl_tpu.ops.pallas.moe_rows import take_rows

    tile, interpret = static
    return take_rows(v, tok, n, tile=tile, interpret=interpret)


def _take_fwd(v, tok, n, static):
    return _take(v, tok, n, static), (tok, n, v.shape[0])


def _take_bwd(static, res, g):
    from sparkdl_tpu.ops.pallas.moe_rows import add_rows

    tile, interpret = static
    tok, n, tokens = res
    d_v = add_rows(g, tok, jnp.ones(tok.shape, jnp.float32), n,
                   tokens=tokens, tile=tile, interpret=interpret)
    return d_v.astype(g.dtype), None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _add(y, weights, w_row, here, order, tok, n, static):
    """(tokens x picks, d) sorted by expert -> (tokens, d) float32: each
    token's picks held here, weighted and summed; of `y` the first `n`
    rows are read. `w_row` is `weights` in the rows' order, as the sort
    carried them there: the gradient comes to `weights`."""
    from sparkdl_tpu.ops.pallas.moe_rows import add_rows

    tile, interpret = static
    return add_rows(y, tok, w_row, n, tokens=here.shape[0], tile=tile,
                    interpret=interpret)


def _add_fwd(y, weights, w_row, here, order, tok, n, static):
    return (_add(y, weights, w_row, here, order, tok, n, static),
            (y, w_row, here, order, tok, n))


def _add_bwd(static, res, g):
    from sparkdl_tpu.ops.pallas.moe_rows import take_rows

    tile, interpret = static
    y, w_row, here, order, tok, n = res
    d_y, dots = take_rows(g, tok, n, w_row, y, tile=tile, interpret=interpret)
    # back to the pairs' order by a sort on the pair each row holds: a
    # gather of 180,000 single numbers takes the TPU six times as long
    _, dots = jax.lax.sort((order, dots), num_keys=1)
    d_weights = jnp.where(here, dots.reshape(here.shape), 0)
    return (d_y, d_weights, jnp.zeros_like(w_row), None, None, None, None)


_take.defvjp(_take_fwd, _take_bwd)
_add.defvjp(_add_fwd, _add_bwd)


@jax.custom_vjp
def _rows_out(v, here, order, inverse):
    """(tokens, d) -> (tokens x picks, d): a token's row once a pick,
    sorted by expert."""
    return v[order // here.shape[1]]


def _rows_out_fwd(v, here, order, inverse):
    return _rows_out(v, here, order, inverse), (here, order, inverse)


def _rows_out_bwd(res, g):
    return _rows_back(g, *res).sum(1), None, None, None


@jax.custom_vjp
def _rows_back(y, here, order, inverse):
    """(tokens x picks, d) sorted by expert -> (tokens, picks, d), zero
    where the pick's expert is not held here."""
    t, k = here.shape
    return jnp.where(here[..., None], y[inverse].reshape(t, k, -1), 0)


def _rows_back_fwd(y, here, order, inverse):
    return _rows_back(y, here, order, inverse), (here, order, inverse)


def _rows_back_bwd(res, g):
    here, order, inverse = res
    g = jnp.where(here[..., None], g, 0)
    return g.reshape(order.shape[0], -1)[order], None, None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)
_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _gated(h):
    gate, up = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(gate) * up


# what lies between an expert's two products: name -> (rows, n) ->
# (rows, d_ff). "gated" takes gate | up side by side from ONE product
FORMS = {"relu2": _relu2, "gated": _gated}


def sorted_experts(v, idx, weights, w_in, w_down, held, interpret=None,
                   form="relu2"):
    """The routed experts' part of a layer that THIS chip's experts
    give: ``sum_k weights_k * e_k(v)`` over the picks whose expert lies
    in ``held``, with ``e(v) = FORMS[form](v @ w_in) @ w_down``.

    :param v: (tokens, d) tokens in the experts' width.
    :param idx, weights: (tokens, picks) of :func:`route_sigmoid` over
        ALL the deployment's experts.
    :param w_in, w_down: the experts held: (count, d, d_ff) and
        (count, d_ff, d); for ``form="gated"`` ``w_in`` is (count, d,
        2 x d_ff), an expert's gate and up matrices side by side.
    :param held: ``(first, count)``: experts ``first .. first + count
        - 1`` live here.
    :param interpret: None: :func:`dispatch_path` decides how the rows
        move; True: the kernels interpreted (tests).
    :returns: ``(out (tokens, d), counts (count,))``, the rows each
        held expert received. The buffer has tokens x picks rows
        whatever the routing, so no pair is ever dropped; the grouped
        products visit ``counts.sum()`` of them, and on the "pallas"
        path so do the row movements around them.
    """
    from sparkdl_tpu.ops.pallas.moe_rows import ROWS_TILE

    first, count = held
    tokens, picks = idx.shape
    kernels = dispatch_path(tokens, v.shape[1], interpret) == "pallas"
    static = (ROWS_TILE, bool(interpret))
    with jax.named_scope("sparkdl.moe.dispatch"):
        local = idx - first
        here = (local >= 0) & (local < count)
        key = jnp.where(here, local, count).reshape(-1)
        # a compare and a sum, not a scatter-add of every pair
        counts = (key[:, None] == jnp.arange(count)).sum(0, dtype=jnp.int32)
        n = counts.sum()
        if kernels:
            # one sort carries each pair's number and weight to its row
            _, order, w_row = jax.lax.sort(
                (key, jnp.arange(key.shape[0], dtype=jnp.int32),
                 jax.lax.stop_gradient(weights).reshape(-1)),
                num_keys=1, is_stable=True)
            tok = order // picks
            rows = _take(v, tok, n, static)
        else:
            order = jnp.argsort(key, stable=True)
            inverse = jnp.argsort(order)
            rows = _rows_out(v, here, order, inverse)
    with jax.named_scope("sparkdl.moe.experts"):
        hidden = FORMS[form](grouped_matmul(rows, w_in, counts))
        rows = grouped_matmul(hidden, w_down, counts)
    with jax.named_scope("sparkdl.moe.dispatch"):
        if kernels:
            out = _add(rows, weights, w_row, here, order, tok, n, static)
        else:
            back = _rows_back(rows, here, order, inverse)
            out = (back.astype(jnp.float32) * weights[..., None]).sum(1)
    return out.astype(v.dtype), counts


# ``relu2`` experts: (v, idx, weights, w_up, w_down, held, interpret=None)
latent_experts = sorted_experts


def _route(module, x):
    """``(idx, weights)`` of the tokens `x` over ALL of ``cfg``'s routed
    experts: the router and its selection bias, as `module`'s own."""
    cfg = module.cfg
    with jax.named_scope("sparkdl.moe.route"):
        logits = nn.Dense(cfg.n_routed_experts, use_bias=False,
                          dtype=jnp.float32, name="router")(x)
        bias = module.param("router_bias", nn.initializers.normal(0.02),
                            (cfg.n_routed_experts,))
        return route_sigmoid(logits, bias, cfg.top_k, cfg.routed_scale)


def _held(module, idx, counts):
    """Sow what each held expert received, and who chose whom: read with
    ``mutable=["intermediates"]`` (the load's shape; the check's
    picks)."""
    module.sow("intermediates", "expert_counts", counts)
    module.sow("intermediates", "picks", idx)


class LatentMoE(nn.Module):
    """Routed experts in a latent width beside a shared expert
    (Nemotron-3's ``E`` layer): sigmoid routing over
    ``n_routed_experts``, non-gated ``relu^2`` experts between a
    projection into ``latent`` and one back, the shared expert on the
    full width. Holds ``experts_held`` of the routed experts and adds
    what THEY give (:func:`sorted_experts`). ``cfg`` is a
    :class:`~sparkdl_tpu.models.hybrid.HybridConfig`."""

    cfg: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        dense = functools.partial(_dense, cfg.attn)
        _, count = cfg.experts_held
        x = u.reshape(-1, u.shape[-1])
        idx, weights = _route(self, x)
        w_up = self.param("w_up", nn.initializers.lecun_normal(batch_axis=0),
                          (count, cfg.latent, cfg.expert_d_ff))
        w_down = self.param("w_down",
                            nn.initializers.lecun_normal(batch_axis=0),
                            (count, cfg.expert_d_ff, cfg.latent))
        # once a traced layer: the share, and the buffer it is built with
        observe.inc("moe.dispatch", held=count, of=cfg.n_routed_experts,
                    picks=cfg.top_k, rows=x.shape[0] * cfg.top_k,
                    product="gmm", form="relu2",
                    path=dispatch_path(x.shape[0], cfg.latent))
        routed, counts = sorted_experts(
            dense(cfg.latent, "latent_in")(x), idx, weights,
            w_up.astype(cfg.dtype), w_down.astype(cfg.dtype),
            cfg.experts_held)
        _held(self, idx, counts)
        out = dense(u.shape[-1], "latent_out")(routed)
        with jax.named_scope("sparkdl.moe.shared"):
            shared = dense(u.shape[-1], "shared_down")(_relu2(
                dense(cfg.shared_d_ff, "shared_up")(x)))
        return (out + shared).reshape(u.shape)


class GatedMoE(nn.Module):
    """Gated routed experts on the full width beside a shared expert of
    the same form (GLM-4.7-Flash's ``G`` layer, the DeepSeek-V3 family's
    expert layer with one group): sigmoid routing over
    ``n_routed_experts``; an expert is ``W_down (silu(W_gate x) * W_up
    x)``, its gate and up matrices held side by side (``w_gate_up``) so
    that the layer is still two grouped products a pass. Holds
    ``experts_held`` of the routed experts and adds what THEY give
    (:func:`sorted_experts`). ``cfg`` is a
    :class:`~sparkdl_tpu.models.hybrid.HybridConfig`."""

    cfg: Any

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        dense = functools.partial(_dense, cfg.attn)
        _, count = cfg.experts_held
        d = u.shape[-1]
        x = u.reshape(-1, d)
        idx, weights = _route(self, x)
        w_gate_up = self.param(
            "w_gate_up", nn.initializers.lecun_normal(batch_axis=0),
            (count, d, 2 * cfg.expert_d_ff))
        w_down = self.param("w_down",
                            nn.initializers.lecun_normal(batch_axis=0),
                            (count, cfg.expert_d_ff, d))
        observe.inc("moe.dispatch", held=count, of=cfg.n_routed_experts,
                    picks=cfg.top_k, rows=x.shape[0] * cfg.top_k,
                    product="gmm", form="gated",
                    path=dispatch_path(x.shape[0], d))
        routed, counts = sorted_experts(
            x, idx, weights, w_gate_up.astype(cfg.dtype),
            w_down.astype(cfg.dtype), cfg.experts_held, form="gated")
        _held(self, idx, counts)
        with jax.named_scope("sparkdl.moe.shared"):
            shared = dense(d, "shared_down")(
                nn.silu(dense(cfg.shared_d_ff, "shared_gate")(x))
                * dense(cfg.shared_d_ff, "shared_up")(x))
        return (routed + shared).reshape(u.shape)
