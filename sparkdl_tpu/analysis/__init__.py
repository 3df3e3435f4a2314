"""``sparkdl_tpu.analysis``: static graph-lint over jaxprs and lowered
StableHLO/HLO, run on the driver *before* a gang spends chip-hours.

The failure modes it exists for are the silent, expensive ones —
collective-order divergence that deadlocks the gang, a lost sharding
constraint that regathers a full TP parameter every step, f64 values
silently canonicalized to f32 (the PR 1 payload-size bug class), and
host callbacks that stall every rank every step.

Entry points:

- :func:`lint_fn` — trace/lower/compile a step and run every pass.
- :func:`lint_lowered` / :func:`lint_compiled` — lint an artifact the
  caller already has (e.g. from
  :func:`sparkdl_tpu.parallel.train.lower_train_step`).
- :func:`lint_gang` — cross-rank collective-consistency over one
  program per rank (the ``per_rank_kwargs`` case).
- the CLI: ``python -m sparkdl_tpu.analysis`` (AST lint over source
  files, ``--self`` for the repo itself, ``--graft N`` for the
  multichip driver program).
- the launcher pre-flight: ``SPARKDL_TPU_PREFLIGHT_LINT=1`` (see
  :mod:`sparkdl_tpu.analysis.preflight`).

Importing this package never imports jax — the launcher touches it on
every gang start and must stay import-light on the driver.
"""

from sparkdl_tpu.analysis.core import (
    Finding,
    GraphContext,
    ParamInfo,
    Severity,
    all_passes,
    max_severity,
    register_pass,
    run_passes,
)
from sparkdl_tpu.analysis.fixes import (
    FIX_ACTIONS,
    FIXIT_SCHEMA,
    Fix,
    fix_program,
)
from sparkdl_tpu.analysis.preflight import (
    PREFLIGHT_ENV,
    PREFLIGHT_FIX_ENV,
    PreflightLintError,
    register_preflight,
)

__all__ = [
    "Finding", "GraphContext", "ParamInfo", "Severity", "all_passes",
    "max_severity", "register_pass", "run_passes", "lint_fn",
    "lint_lowered", "lint_compiled", "lint_gang", "param_info_from",
    "PreflightLintError", "PREFLIGHT_ENV", "PREFLIGHT_FIX_ENV",
    "register_preflight", "register_gang_sharding",
    "Fix", "FIX_ACTIONS", "FIXIT_SCHEMA", "fix_program",
]


def param_info_from(params, shardings):
    """:class:`ParamInfo` list from matching (params, shardings)
    pytrees — params may be arrays or ShapeDtypeStructs; shardings are
    NamedShardings (or PartitionSpec-like). Only axes with mesh size >
    1 count as sharded (XLA normalizes size-1 axes away)."""
    import jax
    from jax.sharding import PartitionSpec

    p_flat, _ = jax.tree_util.tree_flatten_with_path(params)
    s_flat, _ = jax.tree_util.tree_flatten_with_path(
        shardings,
        is_leaf=lambda x: hasattr(x, "spec")
        or isinstance(x, PartitionSpec),
    )
    s_by_path = {jax.tree_util.keystr(p): s for p, s in s_flat}
    out = []
    for path, leaf in p_flat:
        key = jax.tree_util.keystr(path)
        sh = s_by_path.get(key)
        axes = ()
        spec = None
        if sh is not None and hasattr(sh, "spec"):
            spec = sh.spec
        elif isinstance(sh, PartitionSpec):
            # A bare PartitionSpec has no mesh: every named axis
            # counts as sharded (assuming size 1 instead would make
            # the all-gather pass vacuously green).
            spec = sh
        spec_dims = ()
        mesh_axes = ()
        if spec is not None:
            mesh_sizes = dict(
                zip(sh.mesh.axis_names, sh.mesh.devices.shape)
            ) if hasattr(sh, "mesh") else {}
            mesh_axes = tuple(sorted(
                (str(k), int(v)) for k, v in mesh_sizes.items()
            ))
            names = []
            dims = []
            for entry in spec:
                dim_names = []
                for n in (entry if isinstance(entry, tuple) else (entry,)):
                    if n is None:
                        continue
                    dim_names.append(str(n))
                    if mesh_sizes.get(n, 2) > 1:
                        names.append(str(n))
                dims.append(tuple(dim_names))
            axes = tuple(names)
            # The sharding-tree-as-data idiom: the per-dim axis names,
            # padded to the leaf's rank, so the reshard machinery can
            # recompute partition counts under any TARGET mesh.
            dims += [()] * (len(leaf.shape) - len(dims))
            spec_dims = tuple(dims[:len(leaf.shape)])
        out.append(ParamInfo(
            path=key,
            shape=tuple(int(d) for d in leaf.shape),
            dtype=str(leaf.dtype),
            sharded_axes=axes,
            spec=spec_dims,
            mesh_axes=mesh_axes,
        ))
    return out


def _context_for(fn, args, *, compile=True, params=None, shardings=None,
                 mesh=None, name=None, options=None):
    import contextlib

    import jax

    from sparkdl_tpu.utils import jax_compat

    ctx_mgr = mesh if mesh is not None else contextlib.nullcontext()
    jaxpr = hlo_text = stablehlo = memory_stats = compiled = None
    with ctx_mgr:
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception:
            jaxpr = None
        lowered = jax_compat.lower(fn, *args)
        stablehlo = jax_compat.lowered_stablehlo(lowered)
        if compile:
            compiled = lowered.compile()
            hlo_text = compiled.as_text()
            memory_stats = jax_compat.memory_analysis(compiled)
    info = None
    if params is not None and shardings is not None:
        info = param_info_from(params, shardings)
    return GraphContext(
        fn_name=name or getattr(fn, "__name__", "<fn>"),
        jaxpr=jaxpr,
        hlo_text=hlo_text,
        stablehlo_text=stablehlo,
        param_info=info,
        example_args=tuple(args),
        fn=fn,
        x64_enabled=bool(jax.config.jax_enable_x64),
        memory_stats=memory_stats,
        options=options or {},
        lowered=lowered,
        compiled=compiled,
    )


def lint_fn(fn, *args, compile=True, params=None, shardings=None,
            mesh=None, passes=None, name=None, options=None):
    """Trace, lower, (optionally) compile ``fn(*args)`` and run the
    graph passes. ``params``/``shardings`` feed the full-param
    all-gather pass; ``mesh`` is entered around lowering when given.
    Returns findings sorted most-severe first."""
    ctx = _context_for(
        fn, args, compile=compile, params=params, shardings=shardings,
        mesh=mesh, name=name, options=options,
    )
    return run_passes(ctx, passes=passes)


def _lowered_context(lowered, *, params=None, shardings=None,
                     compile=True, name=None, options=None):
    import jax

    from sparkdl_tpu.utils import jax_compat

    info = None
    if params is not None and shardings is not None:
        info = param_info_from(params, shardings)
    hlo_text = memory_stats = compiled = None
    if compile:
        compiled = lowered.compile()
        hlo_text = compiled.as_text()
        memory_stats = jax_compat.memory_analysis(compiled)
    return GraphContext(
        fn_name=name or "<lowered>",
        jaxpr=getattr(lowered, "jaxpr", None),
        hlo_text=hlo_text,
        stablehlo_text=jax_compat.lowered_stablehlo(lowered),
        param_info=info,
        x64_enabled=bool(jax.config.jax_enable_x64),
        memory_stats=memory_stats,
        options=options or {},
        lowered=lowered,
        compiled=compiled,
    )


def lint_lowered(lowered, *, params=None, shardings=None, compile=True,
                 passes=None, name=None, options=None):
    """Lint an existing ``jax.stages.Lowered`` (compiling it for the
    post-partitioning passes unless ``compile=False``)."""
    ctx = _lowered_context(
        lowered, params=params, shardings=shardings, compile=compile,
        name=name, options=options,
    )
    return run_passes(ctx, passes=passes)


def _compiled_context(compiled, *, params=None, shardings=None,
                      name=None, options=None):
    import jax

    from sparkdl_tpu.utils import jax_compat

    info = None
    if params is not None and shardings is not None:
        info = param_info_from(params, shardings)
    return GraphContext(
        fn_name=name or "<compiled>",
        hlo_text=compiled.as_text(),
        param_info=info,
        x64_enabled=bool(jax.config.jax_enable_x64),
        memory_stats=jax_compat.memory_analysis(compiled),
        options=options or {},
        compiled=compiled,
    )


def lint_compiled(compiled, *, params=None, shardings=None, passes=None,
                  name=None, options=None):
    """Lint an already-``Compiled`` executable's optimized HLO."""
    ctx = _compiled_context(
        compiled, params=params, shardings=shardings, name=name,
        options=options,
    )
    return run_passes(ctx, passes=passes)


def register_gang_sharding(params, shardings, mesh=None, *,
                           local_device_count=None, hbm_bytes=None,
                           state_multiplier=3.0):
    """Register the gang's live sharding tree for the supervisor's
    elastic-relaunch pre-flight (``SPARKDL_TPU_GANG_RELAUNCH_NP``):
    before relaunching at a different ``np`` the supervisor runs
    :func:`sparkdl_tpu.analysis.comms.reshard_plan` against this tree
    and refuses an infeasible shrink with a typed
    :class:`~sparkdl_tpu.analysis.comms.ReshardPreflightError` —
    instead of an OOM (or an indivisible-shard crash) mid-restore.

    Driver-side, never pickled — same contract as
    :func:`register_preflight`::

        analysis.register_gang_sharding(params, shardings, mesh)
        HorovodRunner(np=8).run(main)
    """
    from sparkdl_tpu.analysis import comms

    info = param_info_from(params, shardings)
    axes = {}
    if mesh is not None:
        axes = {
            str(k): int(v)
            for k, v in zip(mesh.axis_names, mesh.devices.shape)
        }
    else:
        for i in info:
            axes.update(dict(i.mesh_axes))
    # local_device_count stays explicit-only: the DRIVER's
    # jax.local_device_count() is not the gang's per-host chip count
    # (a driver that forced host devices to lower the program would
    # bake that in and falsely refuse feasible relaunches — a refusal
    # is exactly the failure this gate exists to prevent). Without it
    # the whole-host placement check is skipped, like any other
    # unprovable property.
    return comms.register_gang_sharding(
        info, axes, local_device_count=local_device_count,
        hbm_bytes=hbm_bytes, state_multiplier=state_multiplier,
    )


def lint_gang(fns_or_jaxprs, args_per_rank=None, names=None):
    """Cross-rank collective consistency: one program per rank. Pass
    either ClosedJaxprs, or callables plus ``args_per_rank`` (one args
    tuple per rank) to trace here."""
    from sparkdl_tpu.analysis.passes_collectives import (
        check_gang_consistency,
    )
    import jax

    jaxprs = []
    for i, obj in enumerate(fns_or_jaxprs):
        if callable(obj) and not hasattr(obj, "eqns") \
                and not hasattr(obj, "jaxpr"):
            args = args_per_rank[i] if args_per_rank else ()
            jaxprs.append(jax.make_jaxpr(obj)(*args))
        else:
            jaxprs.append(obj)
    return check_gang_consistency(jaxprs, names=names)
