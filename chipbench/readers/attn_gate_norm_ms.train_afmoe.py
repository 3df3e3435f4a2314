"""Device time a step under ``sparkdl.attn.gate`` and
``sparkdl.attn.qknorm``: the sigmoid gate on the heads' output and the
RMSNorm a head on queries and keys, of every attention mixer and every
pass: what this family's attention costs beside a plain one's."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(
        run, "sparkdl.attn.gate", "sparkdl.attn.qknorm")
    return None if took is None else 1e3 * took
