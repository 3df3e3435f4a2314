"""Verified auto-remediation: turn analysis findings into applied,
semantics-checked program fixes.

The passes in this package *see* waste — an undonated train step
doubling peak HBM, a Python scalar riding into jit weak-typed, a
64-bit payload about to be silently canonicalized — but a finding
that dies as a log line removes nothing. This module closes the loop:
a finding whose rule has a registered *fixer* gets a machine-applicable
:class:`Fix` (action id, preconditions, predicted effect), and the fix
engine applies it at the point the repo constructs programs — re-jit
with inferred ``donate_argnums``, close scalar args over as trace-time
consts, cast 64-bit leaves with an explicit logged cast — then
re-lowers through the same path the launcher/compile-cache consume.

Nothing is trusted: every applied fix carries four machine-checked
proofs, and a fix that cannot produce all four **degrades to the
original finding** — the program is never silently rewritten:

1. **finding eliminated** — the originating pass re-runs on the fixed
   program and its targeted findings are gone;
2. **no new errors** — the FULL pass registry re-runs and no ERROR
   finding appears that the unfixed program did not already have;
3. **numeric equivalence** — both programs execute on a tiny input
   (the example args when concrete and small, bounded by
   ``options["fix_equiv_max_elements"]``) and agree leaf-for-leaf,
   dtype included;
4. **budget delta** — the before/after static budgets
   (:func:`sparkdl_tpu.analysis.comms.comms_report` totals and the
   compiled memory analysis peak) are both computable and the peak
   did not regress.

The machine-readable fixit report (schema
``sparkdl_tpu.analysis.fixit_report/1``) carries all four proofs per
fix and is shared by the CLI (``--fix`` / ``--fix --dry-run``), the
launcher pre-flight (``SPARKDL_TPU_PREFLIGHT_FIX=1``), the gang
telemetry run dir (``fixit_report.json``) and ``observe.doctor``.

Import rule: importing this module never imports jax (the launcher
touches the analysis package on every gang start); jax is reached
lazily inside the engine.
"""

import logging
from dataclasses import dataclass, field

from sparkdl_tpu.analysis import passes_donation as donation_mod
from sparkdl_tpu.analysis.core import Severity, run_passes

logger = logging.getLogger("HorovodRunner")

FIXIT_SCHEMA = "sparkdl_tpu.analysis.fixit_report/1"

# The fixable-rule catalog: rule id -> (action id, one-liner). The
# CLI's --list-rules marks these, docs/analysis.rst documents each
# action, and the docs-drift test pins the two together.
FIX_ACTIONS = {
    "undonated-step-buffers": (
        "donate-step-buffers",
        "infer donate_argnums from the output-multiset analysis and "
        "re-lower with the carried state donated",
    ),
    "host-sync-in-step": (
        "hoist-weak-scalar",
        "close Python-scalar arguments over as jnp.asarray consts at "
        "trace time (callback ERRORs are not auto-fixable)",
    ),
    "silent-canonicalization": (
        "narrow-64bit-payload",
        "explicitly cast 64-bit argument leaves to 32 bits (logged), "
        "refusing any integer that does not round-trip",
    ),
    # Source-level mechanical class from the concurrency lint: the
    # engine cannot rewrite source files, so the action is rendered as
    # a per-site suggestion by `--concur` (concur.render_suggestions)
    # rather than applied by fix_program.
    "thread-lifecycle": (
        "daemonize-unjoined-thread",
        "suggest daemon=True (or a shutdown-path join) for a "
        "non-daemon helper thread that is never joined",
    ),
}

# float64 -> float32 etc. for the narrowing fixer.
_NARROW_DTYPE = {
    "float64": "float32", "int64": "int32", "uint64": "uint32",
    "complex128": "complex64",
}

# Application order when several rules propose fixes on one program:
# argument transforms first (they change the signature the donation
# inference maps onto), the re-jit last.
_ACTION_ORDER = (
    "narrow-64bit-payload", "hoist-weak-scalar", "donate-step-buffers",
)

DEFAULT_EQUIV_MAX_ELEMENTS = 1 << 22


@dataclass(frozen=True)
class Fix:
    """One machine-applicable remediation, attached to the findings it
    targets. ``preconditions`` are the clauses the fixer CHECKED before
    proposing (a fix whose precondition fails is never constructed —
    it degrades instead); ``predicted_effect`` is the static claim the
    budget-delta proof later audits; ``data`` is the action-specific
    machine payload (argnums, leaf paths, dtypes)."""

    rule_id: str
    action: str
    description: str
    preconditions: tuple
    predicted_effect: dict
    data: dict = field(default_factory=dict)
    targets: tuple = ()   # finding dicts this fix eliminates

    def to_dict(self):
        return {
            "rule_id": self.rule_id,
            "action": self.action,
            "description": self.description,
            "preconditions": list(self.preconditions),
            "predicted_effect": dict(self.predicted_effect),
            "data": dict(self.data),
            "targets": [dict(t) for t in self.targets],
        }


@dataclass
class FixAttempt:
    """One rule's remediation attempt: either a verified/applied Fix
    with its four proofs, or a degrade (the original findings stand)."""

    rule_id: str
    action: str
    fix: Fix = None
    verified: bool = False
    applied: bool = False
    degraded: bool = False
    degrade_reason: str = None
    proofs: dict = field(default_factory=dict)
    findings: tuple = ()   # the findings this attempt was about

    def to_dict(self):
        out = {
            "rule_id": self.rule_id,
            "action": self.action,
            "verified": self.verified,
            "applied": self.applied,
            "degraded": self.degraded,
            "proofs": self.proofs,
            "findings": [f.to_dict() for f in self.findings],
        }
        if self.fix is not None:
            out["fix"] = self.fix.to_dict()
        if self.degrade_reason:
            out["degrade_reason"] = self.degrade_reason
        return out


@dataclass
class FixitResult:
    """What :func:`fix_program` hands back: the (possibly rewritten)
    program, its re-lowered artifact, the before/after findings, and
    the machine-readable report."""

    fn: object
    example_args: tuple
    lowered: object
    ctx: object
    findings_before: list
    findings_after: list
    attempts: list
    report: dict


# -- fixers ------------------------------------------------------------------
#
# A fixer inspects the CURRENT program context plus that rule's
# findings and returns ``(Fix, transform)`` — ``transform(fn, args) ->
# (fn2, args2)`` — or ``(None, reason)`` to degrade. Fixers never
# apply anything themselves; the engine owns application and proof.

_FIXERS = {}


def register_fixer(rule_id):
    def deco(fn):
        _FIXERS[rule_id] = fn
        return fn
    return deco


def _flat_arg_offsets(example_args):
    """[(python_argnum, first_flat_index, n_leaves)] — how the entry
    computation's flattened %argN indices map back onto the Python
    positional arguments."""
    import jax

    out = []
    i = 0
    for argnum, a in enumerate(example_args):
        n = len(jax.tree_util.tree_leaves(a))
        out.append((argnum, i, n))
        i += n
    return out


@register_fixer("undonated-step-buffers")
def _fix_donation(ctx, findings):
    """Infer ``donate_argnums`` from the donation pass's own
    output-multiset analysis and re-jit: the fixed step's state
    buffers alias by default. All-or-nothing per Python argument — a
    candidate argument is donated only when EVERY one of its
    still-undonated leaves has an output slot left to alias into
    (donation is per-argument in jax; a partially-coverable argument
    degrades instead of half-donating)."""
    if ctx.fn is None or ctx.example_args is None \
            or ctx.stablehlo_text is None:
        return None, ("the program's callable/example args are not "
                      "available to re-lower")
    args = donation_mod.main_args(ctx.stablehlo_text)
    offsets = _flat_arg_offsets(ctx.example_args)
    total_leaves = sum(n for _, _, n in offsets)
    if len(args) != total_leaves:
        return None, (
            f"entry signature ({len(args)} tensor args) does not map "
            f"1:1 onto the example arguments ({total_leaves} leaves)")
    budget = donation_mod._output_budget(ctx.stablehlo_text, args)
    if ctx.param_info:
        param_sigs = {(i.dtype, i.shape) for i in ctx.param_info}

        def flagged(shape, dtype):
            return (dtype, shape) in param_sigs
    else:
        min_elements = int(ctx.options.get(
            "donation_min_elements", donation_mod.DEFAULT_MIN_ELEMENTS))

        def flagged(shape, dtype):
            return donation_mod._elements(shape) >= min_elements

    by_flat = {idx: (shape, dtype, donated)
               for idx, shape, dtype, donated in args}
    candidates = []
    for argnum, first, n in offsets:
        leaves = [by_flat.get(i) for i in range(first, first + n)]
        if any(entry is None for entry in leaves):
            continue
        hit = any(
            donated is None and shape is not None
            and flagged(shape, dtype)
            for shape, dtype, donated in leaves
        )
        if hit:
            candidates.append((argnum, leaves))
    if not candidates:
        return None, ("no Python argument maps onto the undonated "
                      "buffers")
    # Joint coverage: every still-undonated leaf of a donated argument
    # must find an output slot (consumed as we go). Donation is
    # per-argument in jax, so a candidate that is only PARTIALLY
    # coverable is dropped — not half-donated, and not allowed to
    # veto the fully-coverable candidates (a read-only param-shaped
    # input like an EMA copy must not block donating the real state).
    remaining = dict(budget)
    donate = []
    skipped = []
    saved = 0
    for argnum, leaves in candidates:
        trial = dict(remaining)
        arg_saved = 0
        coverable = True
        for shape, dtype, donated in leaves:
            if donated or shape is None:
                continue
            key = (dtype, shape)
            if trial.get(key, 0) <= 0:
                coverable = False
                break
            trial[key] -= 1
            arg_saved += donation_mod._nbytes(shape, dtype)
        if coverable:
            remaining = trial
            saved += arg_saved
            donate.append(argnum)
        else:
            skipped.append(argnum)
    if not donate:
        return None, (
            f"argument(s) {skipped} are only partially coverable by "
            "the output multiset (a leaf has no output slot left to "
            "alias into); donating a partial argument is not "
            "expressible, so the original finding stands")

    donate = tuple(sorted(donate))
    fix = Fix(
        rule_id="undonated-step-buffers",
        action="donate-step-buffers",
        description=(
            f"re-jit with donate_argnums={donate} so the carried "
            "state's output buffers reuse its input buffers"),
        preconditions=(
            "entry signature maps 1:1 onto the example arguments",
            "every still-undonated leaf of each donated argument has "
            "a same-(dtype, shape) output slot to alias into",
        ),
        predicted_effect={
            "peak_hbm_bytes_saved": saved,
            "donate_argnums": list(donate),
        },
        data={"donate_argnums": list(donate)},
        targets=tuple(f.to_dict() for f in findings),
    )

    def transform(fn, example_args):
        import jax

        return jax.jit(fn, donate_argnums=donate), example_args

    return fix, transform


@register_fixer("host-sync-in-step")
def _fix_weak_scalars(ctx, findings):
    """Hoist Python-scalar arguments out of the call signature: the
    fixed program closes over ``jnp.asarray(value)`` trace-time consts
    (same weak-typed promotion the scalar had — numerics provably
    unchanged — but no retrace-on-type-change hazard and no scalar in
    the payload). Only the WARN-severity scalar findings are fixable;
    callback ERRORs need the callback moved out of the step by hand."""
    scalar_findings = [f for f in findings if f.op in ("int", "float")]
    if not scalar_findings:
        return None, ("host callbacks cannot be auto-removed; move "
                      "them out of the step (or onto a metrics "
                      "cadence outside jit)")
    if ctx.fn is None or ctx.example_args is None:
        return None, ("the program's callable/example args are not "
                      "available to re-trace")
    top_level = {
        i for i, a in enumerate(ctx.example_args)
        if isinstance(a, (int, float)) and not isinstance(a, bool)
    }
    import jax

    n_scalar_leaves = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            tuple(ctx.example_args))[0]:
        if isinstance(leaf, (int, float)) and not isinstance(leaf, bool):
            n_scalar_leaves += 1
    if n_scalar_leaves != len(top_level):
        return None, (
            "a Python scalar is nested inside a container argument; "
            "hoisting it would change the argument pytree — pass a "
            "0-d numpy/jnp array with an explicit dtype instead")
    hoisted = {i: ctx.example_args[i] for i in sorted(top_level)}
    fix = Fix(
        rule_id="host-sync-in-step",
        action="hoist-weak-scalar",
        description=(
            "close over argument position(s) "
            f"{sorted(hoisted)} as jnp.asarray trace-time consts "
            f"(values {list(hoisted.values())!r})"),
        preconditions=(
            "every flagged scalar is a whole top-level positional "
            "argument (nested scalars degrade)",
            "the scalar is constant across calls: the fixed "
            "signature DROPS the argument, so a caller feeding a "
            "varying value (an lr schedule, say) fails loudly on "
            "arity — it is never silently frozen mid-loop",
        ),
        predicted_effect={
            "hoisted_args": len(hoisted),
            "retrace_on_type_change_removed": True,
        },
        data={"argnums": sorted(hoisted),
              "values": {str(k): v for k, v in hoisted.items()}},
        targets=tuple(f.to_dict() for f in scalar_findings),
    )

    def transform(fn, example_args):
        import jax.numpy as jnp

        consts = {i: jnp.asarray(example_args[i]) for i in hoisted}

        def hoisted_fn(*rest):
            it = iter(rest)
            full = tuple(
                consts[i] if i in consts else next(it)
                for i in range(len(example_args))
            )
            return fn(*full)

        pruned = tuple(a for i, a in enumerate(example_args)
                       if i not in consts)
        return hoisted_fn, pruned

    return fix, transform


@register_fixer("silent-canonicalization")
def _fix_narrow_64bit(ctx, findings):
    """Narrow 64-bit argument leaves to 32 bits with an explicit,
    logged cast — the same value truncation jit's canonicalization
    performs silently today, made visible and auditable. Integer
    leaves must round-trip exactly (an int64 above 2**31-1 would
    corrupt, which is precisely the bug class the pass exists for —
    those degrade to the original ERROR)."""
    arg_findings = [f for f in findings
                    if f.severity == Severity.ERROR
                    and f.op in _NARROW_DTYPE]
    if not arg_findings:
        return None, ("only 64-bit argument/payload leaves are "
                      "mechanically narrowable; in-graph 64-bit "
                      "constants (the shadow-trace WARN) need the "
                      "constant pinned in source")
    if ctx.example_args is None:
        return None, "no example arguments to rewrite"
    import jax
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(
        tuple(ctx.example_args))
    paths = [
        jax.tree_util.keystr(p) or "<arg>"
        for p, _ in jax.tree_util.tree_flatten_with_path(
            tuple(ctx.example_args))[0]
    ]
    casts = []   # (flat index, path, src dtype, dst dtype)
    for i, leaf in enumerate(leaves):
        dt = str(getattr(leaf, "dtype", ""))
        if dt not in _NARROW_DTYPE:
            continue
        dst = _NARROW_DTYPE[dt]
        if dt in ("int64", "uint64"):
            arr = np.asarray(leaf)
            if not np.array_equal(
                    arr.astype(dst).astype(dt), arr):
                return None, (
                    f"leaf {paths[i]} is {dt} with values that do not "
                    f"round-trip through {dst}; narrowing would "
                    "corrupt them — split into 32-bit limbs or enable "
                    "x64 instead")
        casts.append((i, paths[i], dt, dst))
    if not casts:
        return None, "no 64-bit leaves found in the example arguments"
    bytes_halved = sum(
        int(np.asarray(leaves[i]).nbytes) // 2 for i, _, _, _ in casts)
    fix = Fix(
        rule_id="silent-canonicalization",
        action="narrow-64bit-payload",
        description=(
            f"explicitly cast {len(casts)} argument leaf/leaves to 32 "
            "bits (the cast jit would otherwise perform silently), "
            "logged per leaf"),
        preconditions=(
            "integer leaves round-trip exactly through the 32-bit "
            "dtype (lossy narrows degrade)",
        ),
        predicted_effect={
            "narrowed_leaves": len(casts),
            "payload_bytes_saved": bytes_halved,
        },
        data={"casts": [
            {"path": p, "from": src, "to": dst} for _, p, src, dst in casts
        ]},
        targets=tuple(f.to_dict() for f in arg_findings),
    )

    def transform(fn, example_args):
        import numpy as np

        lv, td = jax.tree_util.tree_flatten(tuple(example_args))
        for i, path, src, dst in casts:
            logger.info(
                "fixit narrow-64bit-payload: casting %s %s -> %s "
                "(explicit; jit would canonicalize it silently)",
                path, src, dst)
            lv[i] = np.asarray(lv[i]).astype(dst)
        return fn, tuple(jax.tree_util.tree_unflatten(td, lv))

    return fix, transform


# -- the engine --------------------------------------------------------------


def _build_ctx(fn, example_args, *, params=None, shardings=None,
               mesh=None, name=None, options=None, compile=True):
    from sparkdl_tpu.analysis import _context_for

    return _context_for(
        fn, tuple(example_args), compile=compile, params=params,
        shardings=shardings, mesh=mesh, name=name, options=options,
    )


def donated_bytes_static(stablehlo_text):
    """Bytes the entry signature donates (``tf.aliasing_output`` /
    ``jax.buffer_donor`` attrs). The runtime's ``memory_analysis`` is
    authoritative when it carries alias accounting, but an executable
    served from a deserialized XLA persistent-cache entry reports
    ``alias_size_in_bytes`` = 0 even for fully donated programs —
    this static figure (exact: XLA aliases what the attrs request) is
    the fallback that keeps donation visible in the budgets."""
    if not stablehlo_text:
        return 0
    return sum(
        donation_mod._nbytes(shape, dtype)
        for _, shape, dtype, donated
        in donation_mod.main_args(stablehlo_text)
        if donated and shape is not None and dtype is not None)


def peak_bytes(memory_stats, stablehlo_text=None):
    """Static peak of a compiled module from its ``memory_analysis``
    dict: argument + output + temp − aliased. THE one spelling of the
    formula (the budget-delta proof calls it); pass the lowering's
    StableHLO
    to get the :func:`donated_bytes_static` fallback when the alias
    figure reads 0."""
    if not memory_stats:
        return None
    alias = memory_stats.get("alias_size_in_bytes", 0)
    if not alias and stablehlo_text:
        alias = donated_bytes_static(stablehlo_text)
    return (memory_stats.get("argument_size_in_bytes", 0)
            + memory_stats.get("output_size_in_bytes", 0)
            + memory_stats.get("temp_size_in_bytes", 0)
            - alias)


def _copy_args(example_args):
    """A deep device copy of every jax.Array leaf (same sharding), so
    an executed-for-equivalence donated program consumes the COPY's
    buffers, never the caller's."""
    import jax
    import numpy as np

    def cp(x):
        if isinstance(x, jax.Array):
            host = np.asarray(x)
            sharding = getattr(x, "sharding", None)
            if sharding is not None:
                return jax.device_put(host, sharding)
            return jax.device_put(host)
        return x

    return jax.tree_util.tree_map(cp, tuple(example_args))


def _args_concrete_and_small(example_args, max_elements):
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tuple(example_args)):
        if isinstance(leaf, (int, float, bool, complex)):
            continue
        if not hasattr(leaf, "shape") or not hasattr(leaf, "dtype"):
            return False, "non-array argument leaf"
        if isinstance(leaf, jax.ShapeDtypeStruct) or not hasattr(
                leaf, "__array__") and not isinstance(leaf, jax.Array):
            return False, "abstract (shape-only) argument leaf"
        total += int(np.prod(leaf.shape)) if leaf.shape else 1
    if total > max_elements:
        return False, (f"example args hold {total} elements "
                       f"(> fix_equiv_max_elements={max_elements})")
    return True, None


def _equiv_tolerance(dtype):
    import numpy as np

    try:
        eps = float(np.finfo(dtype).eps)
    except ValueError:
        return 0.0, 0.0
    return 64 * eps, 64 * eps


def _numeric_equivalence(orig_fn, orig_args, fixed_fn, fixed_args,
                         mesh=None, max_elements=None):
    """Execute both programs on (copies of) the tiny example input and
    compare leaf-for-leaf, dtype included. Returns the proof dict."""
    import contextlib

    import jax
    import numpy as np

    ok, reason = _args_concrete_and_small(
        orig_args, max_elements or DEFAULT_EQUIV_MAX_ELEMENTS)
    if not ok:
        return {"ok": False, "reason": reason}

    def as_jitted(fn):
        # The program under analysis is the JITTED program — a plain
        # callable must execute through jit so canonicalization /
        # weak-type promotion behave exactly as they would in the
        # step (calling it as raw Python would keep float64 alive and
        # fail every narrowing fix against its own baseline).
        return fn if hasattr(fn, "lower") else jax.jit(fn)

    ctx_mgr = mesh if mesh is not None else contextlib.nullcontext()
    try:
        with ctx_mgr:
            ref = jax.tree_util.tree_map(
                np.asarray, as_jitted(orig_fn)(*_copy_args(orig_args)))
            got = jax.tree_util.tree_map(
                np.asarray, as_jitted(fixed_fn)(*_copy_args(fixed_args)))
    except Exception as e:
        return {"ok": False,
                "reason": f"execution failed ({type(e).__name__}: {e})"}
    ref_leaves, ref_td = jax.tree_util.tree_flatten(ref)
    got_leaves, got_td = jax.tree_util.tree_flatten(got)
    if ref_td != got_td or len(ref_leaves) != len(got_leaves):
        return {"ok": False, "reason": "output pytree structure differs"}
    max_diff = 0.0
    for r, g in zip(ref_leaves, got_leaves):
        r = np.asarray(r)
        g = np.asarray(g)
        if r.dtype != g.dtype:
            return {"ok": False,
                    "reason": f"output dtype drift {r.dtype} -> {g.dtype}"}
        if r.shape != g.shape:
            return {"ok": False,
                    "reason": f"output shape drift {r.shape} -> {g.shape}"}
        if np.issubdtype(r.dtype, np.floating) or np.issubdtype(
                r.dtype, np.complexfloating):
            rtol, atol = _equiv_tolerance(r.dtype)
            wide = r.astype(np.float64) if not np.issubdtype(
                r.dtype, np.complexfloating) else r.astype(np.complex128)
            gw = g.astype(wide.dtype)
            if not np.allclose(wide, gw, rtol=rtol, atol=atol):
                return {"ok": False,
                        "reason": "numeric mismatch beyond tolerance",
                        "max_abs_diff": float(
                            np.max(np.abs(wide - gw)))}
            if wide.size:
                max_diff = max(max_diff,
                               float(np.max(np.abs(wide - gw))))
        else:
            if not np.array_equal(r, g):
                return {"ok": False, "reason": "exact mismatch on "
                        f"{r.dtype} output"}
    return {"ok": True, "max_abs_diff": max_diff,
            "checked_leaves": len(ref_leaves)}


def _budget_delta(before_ctx, after_ctx, name):
    """Before/after static budgets: compiled memory-analysis peak and
    the priced comms totals. ``ok`` requires both sides computable and
    the peak not regressed (a 'fix' that grows peak HBM is no fix)."""
    from sparkdl_tpu.analysis import comms as comms_mod

    out = {"ok": False}
    peak_b = peak_bytes(before_ctx.memory_stats,
                        before_ctx.stablehlo_text)
    peak_a = peak_bytes(after_ctx.memory_stats,
                        after_ctx.stablehlo_text)
    mem = {
        "peak_bytes_before": peak_b,
        "peak_bytes_after": peak_a,
        "peak_bytes_delta": (peak_a - peak_b)
        if peak_a is not None and peak_b is not None else None,
    }
    out["memory"] = mem
    comms = None
    if before_ctx.hlo_text and after_ctx.hlo_text:
        try:
            rb = comms_mod.comms_report(before_ctx.hlo_text, name=name)
            ra = comms_mod.comms_report(after_ctx.hlo_text, name=name)
            comms = {
                "wire_bytes_per_device_before":
                    rb["totals"]["wire_bytes_per_device"],
                "wire_bytes_per_device_after":
                    ra["totals"]["wire_bytes_per_device"],
                "predicted_s_before": rb["totals"]["predicted_s"],
                "predicted_s_after": ra["totals"]["predicted_s"],
            }
        except Exception as e:   # pricing is best-effort evidence
            comms = {"error": f"{type(e).__name__}: {e}"}
    out["comms"] = comms
    if peak_b is None or peak_a is None or comms is None \
            or "error" in comms:
        out["reason"] = "before/after budgets not both computable"
        return out
    # Tiny slack: layout jitter can move peak by a few cache lines.
    if peak_a > peak_b * 1.01 + 4096:
        out["reason"] = (f"peak regressed {peak_b} -> {peak_a} bytes")
        return out
    out["ok"] = True
    return out


def _error_sigs(findings):
    return {(f.rule_id, f.op) for f in findings
            if f.severity >= Severity.ERROR}


def fix_program(fn, example_args, *, params=None, shardings=None,
                mesh=None, options=None, name=None, compile=True,
                apply=True, ctx=None, findings=None):
    """Run the fix engine over one program: lint, propose a fix per
    fixable rule, verify each candidate with the four proofs, and
    (``apply=True``) advance to the fixed program when verification
    holds. Unverifiable fixes degrade — the attempt is reported, the
    original findings stand, and the program is left untouched.

    ``ctx``/``findings`` let a caller that already built the base
    :class:`~sparkdl_tpu.analysis.core.GraphContext` (the CLI's
    ``--graft`` path) skip the duplicate trace/compile.

    Returns a :class:`FixitResult`; ``result.report`` is the
    ``sparkdl_tpu.analysis.fixit_report/1`` document.
    """
    options = dict(options or {})
    name = name or getattr(fn, "__name__", "<fn>")
    if ctx is None:
        ctx = _build_ctx(
            fn, example_args, params=params, shardings=shardings,
            mesh=mesh, name=name, options=options, compile=compile)
    if findings is None:
        findings = run_passes(ctx)
    findings_before = list(findings)

    cur_fn, cur_args, cur_ctx = fn, tuple(example_args), ctx
    cur_findings = list(findings)
    attempts = []
    max_elements = int(options.get(
        "fix_equiv_max_elements", DEFAULT_EQUIV_MAX_ELEMENTS))

    rules_with_findings = {f.rule_id for f in cur_findings}
    ordered_rules = [
        rule for action in _ACTION_ORDER
        for rule, (a, _) in FIX_ACTIONS.items()
        if a == action and rule in rules_with_findings
    ]
    for rule in ordered_rules:
        rule_findings = [f for f in cur_findings if f.rule_id == rule]
        if not rule_findings:
            continue
        action = FIX_ACTIONS[rule][0]
        attempt = FixAttempt(rule_id=rule, action=action,
                             findings=tuple(rule_findings))
        attempts.append(attempt)
        fixer = _FIXERS.get(rule)
        try:
            fix, transform = fixer(cur_ctx, rule_findings)
        except Exception as e:
            fix, transform = None, f"fixer crashed ({type(e).__name__}: {e})"
        if fix is None:
            attempt.degraded = True
            attempt.degrade_reason = transform
            logger.warning(
                "fixit %s/%s degraded to the original finding(s): %s",
                rule, action, transform)
            continue
        attempt.fix = fix
        # Build the candidate program and its context (one lower, one
        # compile) BEFORE any execution.
        try:
            cand_fn, cand_args = transform(cur_fn, cur_args)
            cand_ctx = _build_ctx(
                cand_fn, cand_args, params=params, shardings=shardings,
                mesh=mesh, name=name, options=options, compile=compile)
        except Exception as e:
            attempt.degraded = True
            attempt.degrade_reason = (
                f"fixed program failed to lower ({type(e).__name__}: {e})")
            logger.warning("fixit %s/%s degraded: %s", rule, action,
                           attempt.degrade_reason)
            continue

        # Proof 1: the originating pass, re-run on the fixed program,
        # no longer emits the targeted findings.
        try:
            remaining = run_passes(cand_ctx, passes=[rule])
        except Exception:
            remaining = run_passes(cand_ctx)
            remaining = [f for f in remaining if f.rule_id == rule]
        target_sigs = {(t["rule_id"], t["severity"], t["op"])
                       for t in (dict(t) for t in fix.targets)}
        still = [f for f in remaining
                 if (f.rule_id, f.severity.name, f.op) in target_sigs]
        proof1 = {"ok": not still, "remaining": len(still)}

        # Proof 2: full registry, no NEW ERROR findings.
        cand_findings = run_passes(cand_ctx)
        new_errors = sorted(
            _error_sigs(cand_findings) - _error_sigs(cur_findings))
        proof2 = {"ok": not new_errors,
                  "new_errors": [list(s) for s in new_errors]}

        # Proof 3: tiny-input numeric equivalence vs the unfixed
        # program.
        proof3 = _numeric_equivalence(
            cur_fn, cur_args, cand_fn, cand_args, mesh=mesh,
            max_elements=max_elements)

        # Proof 4: before/after budget delta (memory peak + comms).
        proof4 = _budget_delta(cur_ctx, cand_ctx, name)

        attempt.proofs = {
            "finding_eliminated": proof1,
            "no_new_errors": proof2,
            "numeric_equivalence": proof3,
            "budget_delta": proof4,
        }
        attempt.verified = all(
            p.get("ok") for p in attempt.proofs.values())
        if not attempt.verified:
            attempt.degraded = True
            failed = [k for k, p in attempt.proofs.items()
                      if not p.get("ok")]
            attempt.degrade_reason = (
                "verification failed (" + ", ".join(failed) + "); the "
                "original finding stands")
            logger.warning("fixit %s/%s degraded: %s", rule, action,
                           attempt.degrade_reason)
            continue
        # Verified: advance the cursor. ``applied`` records whether
        # the caller asked for the fixed program (dry-run verifies the
        # same proofs but hands the original program back).
        attempt.applied = bool(apply)
        cur_fn, cur_args, cur_ctx = cand_fn, cand_args, cand_ctx
        cur_findings = cand_findings
        logger.info(
            "fixit %s/%s %s: %s", rule, action,
            "applied" if apply else "verified (dry-run)",
            fix.description)

    # "Unfixable" = findings no VERIFIED fix targeted — by identity,
    # not rule id: a callback ERROR shares host-sync-in-step's rule
    # with the hoistable scalar WARNs but survives the hoist, and
    # must still show up in the remediation story's unfixable bucket.
    fixed_targets = [dict(t) for a in attempts if a.verified and a.fix
                     for t in a.fix.targets]
    unfixable = [f for f in findings_before
                 if f.to_dict() not in fixed_targets]
    report = {
        "schema": FIXIT_SCHEMA,
        "name": name,
        "mode": "apply" if apply else "dry-run",
        "fixes": [a.to_dict() for a in attempts],
        "unfixable": [f.to_dict() for f in unfixable],
        "findings_before": [f.to_dict() for f in findings_before],
        "findings_after": [f.to_dict() for f in cur_findings],
        "summary": {
            "proposed": len(attempts),
            "verified": sum(1 for a in attempts if a.verified),
            "applied": sum(1 for a in attempts if a.applied),
            "degraded": sum(1 for a in attempts if a.degraded),
            "findings_before": len(findings_before),
            "findings_after": len(cur_findings),
        },
    }
    if not apply:
        # Dry-run hands the ORIGINAL program back — the proofs were
        # produced against real fixed candidates, but nothing the
        # caller holds was rewritten (ctx/lowered included: a caller
        # compiling result.lowered must get the unfixed program).
        cur_fn, cur_args, cur_ctx = fn, tuple(example_args), ctx
    return FixitResult(
        fn=cur_fn,
        example_args=cur_args,
        lowered=getattr(cur_ctx, "lowered", None),
        ctx=cur_ctx,
        findings_before=findings_before,
        findings_after=cur_findings,
        attempts=attempts,
        report=report,
    )


def render_fixit_text(report):
    """Human-readable fixit table (the CLI text mode and
    ``observe.doctor`` both render from the same report)."""
    s = report.get("summary", {})
    lines = [
        f"fixit [{report.get('name')}] ({report.get('mode')}): "
        f"{s.get('proposed', 0)} fix(es) proposed, "
        f"{s.get('verified', 0)} verified, "
        f"{s.get('applied', 0)} applied, "
        f"{s.get('degraded', 0)} degraded; findings "
        f"{s.get('findings_before', 0)} -> {s.get('findings_after', 0)}"
    ]
    for entry in report.get("fixes", ()):
        state = ("applied" if entry.get("applied")
                 else "verified" if entry.get("verified")
                 else "degraded")
        line = f"  [{state}] {entry['rule_id']} -> {entry['action']}"
        fix = entry.get("fix")
        if fix:
            line += f": {fix['description']}"
        if entry.get("degrade_reason"):
            line += f" ({entry['degrade_reason']})"
        lines.append(line)
        proofs = entry.get("proofs") or {}
        if proofs:
            mem = (proofs.get("budget_delta") or {}).get("memory") or {}
            delta = mem.get("peak_bytes_delta")
            bits = [
                f"{k}={'ok' if (v or {}).get('ok') else 'FAIL'}"
                for k, v in proofs.items()
            ]
            if delta is not None:
                bits.append(f"peak {delta / 2**20:+.2f} MiB")
            lines.append("      proofs: " + ", ".join(bits))
    return "\n".join(lines)
