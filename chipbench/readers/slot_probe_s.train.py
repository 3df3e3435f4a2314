"""Sum of the ``gang.slot_probe`` spans the harness process recorded:
its own call of ``launcher.probe_local_devices`` (a child that reaches
the chip and exits) and the runner's, which the cache answers."""

from chipbench import launch_spans


def read(run):
    spans = launch_spans.of(run)
    probes = launch_spans.named(spans or [], "gang.slot_probe")
    if not probes:
        return None
    return sum(s["end"] - s["start"] for s in probes)
