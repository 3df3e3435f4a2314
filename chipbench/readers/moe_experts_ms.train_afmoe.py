"""Device time a step under ``sparkdl.moe.experts``: the two grouped
products over the 128 experts held and ``silu(gate) * up`` between
them, of every expert layer, forward, the remat's forward again and
backward."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.moe.experts")
    return None if took is None else 1e3 * took
