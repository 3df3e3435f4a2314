"""The slowest rank's ``hvd.init`` span: for a gang it holds
``jax.distributed.initialize`` and the rank exchange, for one worker
next to nothing."""

from chipbench import launch_spans


def read(run):
    inits = launch_spans.named(
        launch_spans.of(run) or [], "hvd.init", workers=True)
    if not inits:
        return None
    return max(s["end"] - s["start"] for s in inits)
