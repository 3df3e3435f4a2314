"""Device time a step under ``sparkdl.moe.route`` and
``sparkdl.moe.dispatch``: scores, top-k, the sort of the (token, pick)
pairs, the gather of their rows and the weighted sum back to tokens, of
every expert layer and every pass."""

from chipbench import hybrid_scopes


def read(run):
    took = hybrid_scopes.step_seconds(
        run, "sparkdl.moe.route", "sparkdl.moe.dispatch")
    return None if took is None else 1e3 * took
