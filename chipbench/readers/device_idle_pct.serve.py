"""1 - the union of device-operation intervals over the traced stretch
of the window (about five seconds in its middle)."""

from chipbench.trace_reduce import idle_pct


def read(run):
    return idle_pct(run["trace"]) if run.get("trace") else None
