"""A ``train_hybrid`` run's device time by the program's scopes
(``kinds/train_hybrid.py`` puts ``scopes.by_scope``'s table of the
traced steps under ``run["by_scope"]``).

``scopes.SCOPE`` ends a scope's name at its second dot, and the
program's are dotted (``sparkdl.ssm.scan``): a name stack is flattened
first, so the table's rows read ``sparkdl.ssm_scan``."""

import re

DOTTED = re.compile(r"(sparkdl\.[A-Za-z0-9_]+)\.([A-Za-z0-9_]+)")


def flatten(stack):
    """``.../sparkdl.moe/sparkdl.moe.route/...`` with the inner scope
    as ``sparkdl.moe_route``, which ``scopes.scope_of`` keeps whole."""
    return DOTTED.sub(r"\1_\2", stack)


def step_seconds(run, *names):
    """Device seconds ONE traced step of `run` spent under the scopes
    `names` (as the program writes them), all passes; None where the
    run has no table or none of the scopes is in it (the parent commit
    of PR 29 has none of them)."""
    table = run.get("by_scope") or {}
    found = [table[flatten(name)]["total"] for name in names
             if flatten(name) in table]
    return sum(found) if found else None
