"""Pipeline parallelism: GPipe-style microbatch streaming over a
``stage`` mesh axis.

Each device owns one stage's parameters (the stacked per-stage param
tree is sharded on its leading axis); microbatches enter at stage 0,
ride neighbor-to-neighbor ``ppermute`` hops (pure ICI traffic) through
the stages, and the final stage's outputs are collected.

Two hop schedules:

- ``overlap=True`` (default): the stage-to-stage hop is software-
  pipelined — each tick's ``ppermute`` ships the PREVIOUS tick's
  output while this tick's ``stage_fn`` computes on the activation
  that already arrived, so the wire transfer and the stage compute
  have no data dependence inside the tick and XLA's async collective
  scheduler can overlap them. An activation spends one compute tick
  plus one (hidden) transit tick per stage, so the schedule runs
  ``M + 2(P-1)`` ticks — bubble fraction ``2(P-1)/(M+2(P-1))``; pick
  M >= 8P to keep >80% utilization. Worth it exactly when the hop is
  ICI-bound: the serialized schedule pays the full wire latency on
  every tick of every stage.
- ``overlap=False``: the legacy serialized schedule — ``stage_fn``
  then the hop inside one tick, ``M + P - 1`` ticks, every hop a
  barrier between two ticks' compute.

Both schedules apply the same stage compositions to the same
microbatches — outputs are identical (pinned by tests).

Differentiable end to end: JAX transposes ``ppermute``/``scan``
automatically, so ``jax.grad`` through :func:`pipeline_apply` yields
the standard GPipe backward schedule without extra code — idiomatic
XLA pipelining rather than a hand-scheduled runtime (the reference has
no pipeline parallelism at all, SURVEY.md §2.3).
"""

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stacked_params, microbatches, *,
                   axis_name="stage", overlap=None):
    """Run inside ``shard_map``: stream microbatches through stages.

    :param stage_fn: ``f(params_i, x) -> y`` applied by each stage
        (y.shape == x.shape — e.g. a group of transformer blocks).
    :param stacked_params: this device's stage params, leading axis 1
        (the shard of a (P, ...) stacked tree).
    :param microbatches: (M, mb, ...) — replicated across stages; only
        stage 0 reads them.
    :param overlap: software-pipelined hop schedule (default; ``None``
        resolves the ``SPARKDL_TPU_OVERLAP`` env knob) vs the
        serialized legacy lowering (see module docstring).
    :return: (M, mb, ...) outputs, replicated (psum-collected from the
        last stage).
    """
    from sparkdl_tpu.parallel.ring_attention import resolve_overlap

    overlap = resolve_overlap(overlap)
    n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = microbatches.shape[0]
    params_local = jax.tree.map(lambda x: x[0], stacked_params)
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    mb_shape = microbatches.shape[1:]
    # ticks an activation needs to clear the pipe: one compute tick
    # per stage, plus (overlap) one transit tick per hop
    lag = (2 if overlap else 1) * (n_stages - 1)
    n_ticks = m + lag

    def inject(cur, t):
        # stage 0 injects microbatch t (while t < m)
        mb = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, m - 1), axis=0, keepdims=False
        )
        return jnp.where(jnp.logical_and(stage == 0, t < m), mb, cur)

    def collect(outputs, y, t):
        # last stage collects finished microbatch t - lag
        out_idx = t - lag
        take = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
        return jax.lax.cond(
            take,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.maximum(out_idx, 0), axis=0
            ),
            lambda o: o,
            outputs,
        )

    if overlap:
        def tick(carry, t):
            cur, sent, outputs = carry
            # ship the PREVIOUS tick's output first: the hop's only
            # dependence is an already-computed activation, so it
            # rides the interconnect while stage_fn computes below
            recv = jax.lax.ppermute(sent, axis_name, perm)
            cur = inject(cur, t)
            y = stage_fn(params_local, cur)
            outputs = collect(outputs, y, t)
            # next tick computes on what just arrived and ships y
            return (recv, y, outputs), None
    else:
        def tick(carry, t):
            cur, outputs = carry
            cur = inject(cur, t)
            y = stage_fn(params_local, cur)
            outputs = collect(outputs, y, t)
            # hop to the next stage (ICI neighbor exchange)
            cur = jax.lax.ppermute(y, axis_name, perm)
            return (cur, outputs), None

    cur0 = jnp.zeros(mb_shape, microbatches.dtype)
    out0 = jnp.zeros((m,) + mb_shape, microbatches.dtype)
    carry0 = (cur0, cur0, out0) if overlap else (cur0, out0)
    carry, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
    outputs = carry[-1]
    # replicate the last stage's collected outputs to every stage
    keep = (stage == n_stages - 1).astype(outputs.dtype)
    return jax.lax.psum(outputs * keep, axis_name)


def make_pipeline(mesh, stage_fn, *, axis_name="stage", overlap=True):
    """Bind a pipeline to a mesh: returns ``f(stacked_params,
    microbatches) -> outputs`` on GLOBAL arrays, where stacked_params'
    leading axis (= number of stages) is sharded over ``axis_name`` and
    microbatches are replicated. ``overlap`` selects the hop schedule
    (see :func:`pipeline_apply`)."""

    def run(stacked_params, microbatches):
        return pipeline_apply(
            stage_fn, stacked_params, microbatches, axis_name=axis_name,
            overlap=overlap,
        )

    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    def spec_for(leaf):
        return P(axis_name, *([None] * (leaf.ndim - 1)))

    def call(stacked_params, microbatches):
        in_specs = (
            jax.tree.map(spec_for, stacked_params),
            P(),
        )

        fn = jax.shard_map(
            run, mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False,
        )
        return fn(stacked_params, microbatches)

    call.n_stages = n_stages
    return call
