"""Silent-canonicalization pass.

With ``jax_enable_x64`` off (the default on every TPU rig), every
64-bit value is silently canonicalized to 32 bits at trace time. For
f64→f32 that means integers above 2**24 stop round-tripping — exactly
the bug class PR 1 fixed, where collective payload *sizes* rode a
float64 array and 16.7MB–2GiB payloads were rounded for months without
a single warning.

Two detectors, because canonicalization happens before a jaxpr exists
(the 64-bit-ness is invisible in the traced program):

1. **argument dtypes** — any example-arg leaf (or shipped payload
   leaf) that is a 64-bit numpy array/scalar will be canonicalized the
   moment it enters jit; flagged ERROR with the 2**24 rounding story.
2. **x64 shadow trace** — re-``eval_shape`` the same function under
   ``jax.experimental.enable_x64()``: any output whose dtype *changes*
   proves a strongly-typed 64-bit constant or op inside the function
   is being silently downcast today.
"""

from sparkdl_tpu.analysis.core import Finding, Severity, register_pass

_RULE = "silent-canonicalization"

_64BIT = ("float64", "int64", "uint64", "complex128")


def _leaf_dtype(leaf):
    dt = getattr(leaf, "dtype", None)
    if dt is not None:
        return str(dt)
    # Python scalars are weak-typed, not canonicalized — not ours.
    return None


def payload_findings(tree, where="payload"):
    """64-bit leaves in a pytree headed for a jitted step (no tracing
    required — usable on raw HorovodRunner kwargs)."""
    import jax

    findings = []
    leaves_with_path, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in leaves_with_path:
        dt = _leaf_dtype(leaf)
        if dt in _64BIT:
            key = jax.tree_util.keystr(path) or "<root>"
            findings.append(Finding(
                rule_id=_RULE,
                severity=Severity.ERROR,
                op=dt,
                location="",
                message=(
                    f"{where} leaf {key} is {dt} but jax_enable_x64 is "
                    "off: it will be silently canonicalized to 32 bits "
                    "inside jit (f64→f32 rounds every integer above "
                    "2**24 — the payload-size bug class). Cast "
                    "explicitly, split into 32-bit limbs, or enable "
                    "x64."
                ),
            ))
    return findings


@register_pass(_RULE, requires=("example_args",),
               severities=("ERROR", "WARNING"))
def silent_canonicalization(ctx):
    """Flag 64-bit inputs and in-graph 64-bit constants that
    canonicalize to 32 bits with x64 off."""
    import jax

    if ctx.x64_enabled or (
        ctx.x64_enabled is None and jax.config.jax_enable_x64
    ):
        return []
    findings = payload_findings(ctx.example_args, where="argument")

    if ctx.fn is not None:
        findings.extend(_shadow_trace_findings(ctx))
    return findings


def _shadow_trace_findings(ctx):
    import jax

    try:
        base = jax.eval_shape(ctx.fn, *ctx.example_args)
        # Pin the arg avals to their canonicalized (32-bit) dtypes
        # BEFORE entering x64, so only *internal* 64-bit constants/ops
        # may widen — any dtype drift is then inside fn, not the args.
        pinned = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
            jax.eval_shape(lambda *a: a, *ctx.example_args),
        )
        with jax.enable_x64(True):
            wide = jax.eval_shape(ctx.fn, *pinned)
    except Exception as e:  # tracing is user code; never let it throw
        return [Finding(
            rule_id=_RULE,
            severity=Severity.INFO,
            op="shadow-trace",
            location="",
            message=(
                "x64 shadow trace could not run "
                f"({type(e).__name__}: {e}); in-graph f64 constants "
                "were not checked."
            ),
        )]
    findings = []
    base_flat, _ = jax.tree_util.tree_flatten_with_path(base)
    wide_flat, _ = jax.tree_util.tree_flatten_with_path(wide)
    if len(base_flat) != len(wide_flat):
        return findings
    import jax.tree_util as jtu

    for (path, b), (_, w) in zip(base_flat, wide_flat):
        bd, wd = str(getattr(b, "dtype", "")), str(getattr(w, "dtype", ""))
        if bd != wd and wd in _64BIT:
            key = jtu.keystr(path) or "<output>"
            # WARNING, not ERROR: drift can also come from library
            # defaults that follow x64 (e.g. one_hot's float default),
            # where no real 64-bit data exists to lose. Real 64-bit
            # *data* entering the step is the arg-level ERROR above.
            findings.append(Finding(
                rule_id=_RULE,
                severity=Severity.WARNING,
                op=f"{wd}->{bd}",
                location="",
                message=(
                    f"output {key} computes as {wd} when x64 is "
                    f"allowed but is silently canonicalized to {bd} "
                    "today: a strongly-typed 64-bit constant or op "
                    "inside the step is being downcast (f64→f32 "
                    "rounds integers above 2**24). Pin the constant "
                    "to 32 bits explicitly if this is intended."
                ),
            ))
    return findings
