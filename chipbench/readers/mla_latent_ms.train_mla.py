"""Device time a step under ``sparkdl.mla.latent``: the four low-rank
projections, both latent norms, rope, the broadcast of the shared rope
key and the concatenations that make a head's queries and keys, of
every latent-attention mixer and every pass."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(run, "sparkdl.mla.latent")
    return None if took is None else 1e3 * took
