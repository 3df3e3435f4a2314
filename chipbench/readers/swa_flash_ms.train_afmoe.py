"""Device time a step under ``sparkdl.attn.window``: the window
layers' rope, K/V repeat, transposes, the three flash kernels (forward,
the remat's forward again, dq, dk/dv) and ``delta``, whatever
implements them, of every window mixer and every pass."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.attn.window")
    return None if took is None else 1e3 * took
