"""Collective-safety passes: gang deadlocks and full-param gathers.

A TPU gang dies two ways that compile cleanly and dryrun green:

- ranks disagree on the *order* of collectives (a collective inside
  one branch of a data-dependent ``cond``, a ``while`` whose trip
  count differs per rank) → every rank blocks in a different
  collective, forever — ICI collectives have no timeout;
- XLA rematerializes a *fully-replicated* copy of a tensor-parallel
  parameter every step (the classic lost-constraint TP regression) —
  still correct numerics, catastrophic HBM/interconnect cost at real
  scale, invisible on tiny dryrun shapes.
"""

import re

from sparkdl_tpu.analysis import hlo as hlo_mod
from sparkdl_tpu.analysis import jaxpr_walk
from sparkdl_tpu.analysis.core import Finding, Severity, register_pass


@register_pass("collective-consistency", requires=("jaxpr",),
               severities=("ERROR", "WARNING"))
def collective_consistency(ctx):
    """Flag control flow under which ranks could execute divergent
    collective sequences (gang deadlock)."""
    findings = []
    for eqn, path in jaxpr_walk.iter_eqns(ctx.jaxpr):
        name = eqn.primitive.name
        if name == "cond":
            branches = eqn.params.get("branches", ())
            sigs = [jaxpr_walk.signature(b) for b in branches]
            if len(set(sigs)) > 1:
                desc = "; ".join(
                    f"branch {i}: "
                    + (", ".join(f"{p}({'/'.join(a)})" for p, a, _ in s)
                       or "<none>")
                    for i, s in enumerate(sigs)
                )
                findings.append(Finding(
                    rule_id="collective-consistency",
                    severity=Severity.ERROR,
                    op="cond",
                    location=jaxpr_walk.source_location(eqn),
                    message=(
                        "collective sequence differs between cond "
                        f"branches ({desc}): ranks whose predicate "
                        "disagrees enter different collectives and the "
                        "gang deadlocks (ICI collectives never time "
                        "out). Hoist the collectives out of the cond "
                        "or make every branch issue the same sequence."
                    ),
                ))
        elif name == "while":
            body_sig = ()
            for key in ("body_jaxpr", "cond_jaxpr"):
                sub = eqn.params.get(key)
                if sub is not None:
                    body_sig += jaxpr_walk.signature(sub)
            if body_sig:
                ops = ", ".join(
                    f"{p}({'/'.join(a)})" for p, a, _ in body_sig
                )
                findings.append(Finding(
                    rule_id="collective-consistency",
                    severity=Severity.WARNING,
                    op="while",
                    location=jaxpr_walk.source_location(eqn),
                    message=(
                        f"collective(s) [{ops}] inside a dynamic-trip-"
                        "count while loop: if any rank's trip count "
                        "diverges, the gang deadlocks. Prefer "
                        "lax.scan (static length) or prove the "
                        "predicate is replicated."
                    ),
                ))
    return findings


def hlo_role_divergence(hlo_text):
    """Cross-role divergence in one partitioned module: roles (device
    groups) whose ordered (kind, dtype) collective sequences differ.
    Exposed for callers holding only HLO text; within a single SPMD
    module every device runs the same op stream, so this only fires on
    modules stitched from divergent per-rank programs."""
    roles = hlo_mod.role_sequences(hlo_mod.collectives(hlo_text))
    stripped = {
        role: [(k, d) for k, d, _ in seq] for role, seq in roles.items()
    }
    if len({tuple(s) for s in stripped.values()}) <= 1:
        return []
    desc = "; ".join(
        f"devices {sorted(map(str, role))}: "
        + (", ".join(f"{k}[{d}]" for k, d in seq) or "<none>")
        for role, seq in sorted(stripped.items(), key=str)
    )
    return [Finding(
        rule_id="collective-consistency",
        severity=Severity.ERROR,
        op="module",
        location="",
        message=(
            f"mesh roles disagree on the collective sequence ({desc}); "
            "the gang deadlocks at the first mismatched op."
        ),
    )]


def check_gang_consistency(jaxprs, names=None):
    """Cross-rank divergence: every rank of a gang must lower the SAME
    ordered collective sequence. Give one (Closed)Jaxpr per rank (e.g.
    the per-rank programs behind ``per_rank_kwargs``); a mismatch is
    an ERROR naming the first diverging position."""
    sigs = [jaxpr_walk.signature(j) for j in jaxprs]
    if not sigs:
        return []
    names = names or [f"rank {i}" for i in range(len(sigs))]
    base = sigs[0]
    findings = []
    for name, sig in zip(names[1:], sigs[1:]):
        if sig == base:
            continue
        pos = next(
            (i for i, (a, b) in enumerate(zip(base, sig)) if a != b),
            min(len(base), len(sig)),
        )

        def at(s, i):
            if i >= len(s):
                return "<end of program>"
            p, axes, d = s[i]
            return f"{p}({'/'.join(axes)})[{d}]"

        findings.append(Finding(
            rule_id="collective-consistency",
            severity=Severity.ERROR,
            op="gang",
            location="",
            message=(
                f"{names[0]} and {name} diverge at collective #{pos}: "
                f"{at(base, pos)} vs {at(sig, pos)} — a gang whose "
                "ranks disagree on the collective order deadlocks at "
                "the first mismatch."
            ),
        ))
    return findings


_GATHER_DIM_RE = re.compile(r"\bdimensions=\{(\d+)\}")


@register_pass("full-param-allgather",
               requires=("hlo_text", "param_info"),
               severities=("ERROR", "WARNING"))
def full_param_allgather(ctx):
    """Flag all-gathers that materialize a fully-replicated copy of a
    TP-sharded parameter (generalizes the tests/test_graft_entry.py
    HLO grep).

    Tiers:

    - ERROR — the gather result is *exactly* a TP-sharded param's
      full (dtype, shape), gathered along a dimension the param is
      sharded on: XLA is rematerializing the unsharded weight, i.e. a
      lost sharding constraint. A gather along a dimension the param
      keeps whole cannot rebuild it — that is an activation whose
      per-device shape happens to equal the param's (the graft
      driver's (batch/data, seq, 16) pipeline input against its
      (stages, 16, 16) pipeline weight).
    - WARNING — same dims in a different order (a relaid-out /
      transposed full copy), which is how the regather shows up when
      XLA also changed the layout.
    - optional size bound: ``ctx.options["allgather_max_elements"]``
      reinstates the original grep's blunt rule — any all-gather of a
      TP dtype at/above the bound is a WARNING. Off by default (on
      programs whose smallest TP param is tiny — LoRA adapters — a
      raw size bound drowns real findings in activation noise).
    """
    tp_params = [p for p in ctx.param_info if p.sharded_axes]
    if not tp_params:
        return []
    by_shape = {}
    by_sorted = {}
    for p in tp_params:
        dt = hlo_mod.to_hlo_dtype(p.dtype)
        by_shape.setdefault((dt, p.shape), []).append(p)
        by_sorted.setdefault((dt, tuple(sorted(p.shape))), []).append(p)
    tp_dtypes = {hlo_mod.to_hlo_dtype(p.dtype) for p in tp_params}
    size_bound = ctx.options.get("allgather_max_elements")
    findings = []
    for col in hlo_mod.collectives(ctx.hlo_text):
        if col.kind != "all-gather":
            continue
        for dtype, shape in col.result_types:
            n = 1
            for d in shape:
                n *= d
            gdim = _GATHER_DIM_RE.search(col.line)
            gdim = int(gdim.group(1)) if gdim else None
            exact = [
                p for p in by_shape.get((dtype, shape), ())
                if gdim is None or gdim >= len(p.spec) or p.spec[gdim]
            ]
            relaid = by_sorted.get((dtype, tuple(sorted(shape))))
            if exact:
                names = ", ".join(p.path for p in exact)
                findings.append(Finding(
                    rule_id="full-param-allgather",
                    severity=Severity.ERROR,
                    op="all-gather",
                    location="",
                    message=(
                        f"all-gather result {dtype}{list(shape)} is "
                        f"exactly the full shape of TP-sharded "
                        f"param(s) [{names}]: XLA is rematerializing "
                        "the unsharded weight every step — a lost "
                        "sharding constraint. HLO: "
                        + col.line[:160]
                    ),
                ))
            elif relaid:
                names = ", ".join(p.path for p in relaid)
                findings.append(Finding(
                    rule_id="full-param-allgather",
                    severity=Severity.WARNING,
                    op="all-gather",
                    location="",
                    message=(
                        f"all-gather result {dtype}{list(shape)} has "
                        f"the full dims (reordered) of TP-sharded "
                        f"param(s) [{names}] — likely a relaid-out "
                        "fully-replicated copy of the weight. HLO: "
                        + col.line[:160]
                    ),
                ))
            elif size_bound is not None and dtype in tp_dtypes \
                    and n >= size_bound:
                findings.append(Finding(
                    rule_id="full-param-allgather",
                    severity=Severity.WARNING,
                    op="all-gather",
                    location="",
                    message=(
                        f"all-gather result {dtype}{list(shape)} "
                        f"({n} elements) reaches the configured bound "
                        f"({size_bound}) — check it is an activation, "
                        "not a regathered weight. HLO: "
                        + col.line[:160]
                    ),
                ))
    return findings
