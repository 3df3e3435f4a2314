"""``child_backend_s`` + ``child_exit_s`` of the harness process's
uncached ``gang.slot_probe``: the probe child's reach of the chip and
its exit (teardown, the chip's release): the seconds a probe that
started no runtime would not spend."""

from chipbench import launch_spans


def read(run):
    probes = [s["args"] for s in launch_spans.named(
        launch_spans.of(run) or [], "gang.slot_probe")
        if "child_backend_s" in s["args"]]
    if not probes:
        return None
    return sum(a["child_backend_s"] + a["child_exit_s"] for a in probes)
