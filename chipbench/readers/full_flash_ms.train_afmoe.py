"""Device time a step under ``sparkdl.attn.full``: the full layers'
K/V repeat, transposes, the three flash kernels (forward, the remat's
forward again, dq, dk/dv) and ``delta``, of every full-attention mixer
and every pass."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.attn.full")
    return None if took is None else 1e3 * took
