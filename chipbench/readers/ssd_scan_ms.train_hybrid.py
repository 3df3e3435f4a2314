"""Device time a step under ``sparkdl.ssm.scan``: the state-space scan
of every Mamba-2 layer, forward, the remat's forward again and
backward."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.ssm.scan")
    return None if took is None else 1e3 * took
