"""The slowest rank's ``worker.backend`` span: a worker's reach of its
chip (the first question asked of the devices starts the backend),
before it reports READY, so inside ``launch_s.train``."""

from chipbench import launch_spans


def read(run):
    reaches = launch_spans.named(
        launch_spans.of(run) or [], "worker.backend", workers=True)
    if not reaches:
        return None
    return max(s["end"] - s["start"] for s in reaches)
