"""``gang.spawn`` start to the slowest rank's ``worker.boot`` end (its
``worker.start``): fork, interpreter, the package's and JAX's imports."""

from chipbench import launch_spans


def read(run):
    spans = launch_spans.of(run) or []
    spawn = launch_spans.named(spans, "gang.spawn")
    boots = launch_spans.named(spans, "worker.boot", workers=True)
    if not spawn or not boots:
        return None
    return max(s["end"] for s in boots) - spawn[-1]["start"]
