"""Sum of rank 0's ``xla.compile`` spans that end before the window
starts (``spec["started"]`` + ``setup_s``): every program's backend
compile or load from the persistent cache (weights' init, the step,
the batches' transfers), where ``compile_s.train`` times the step's
alone. The reference check compiles after the window: not counted."""

from chipbench import launch_spans


def read(run):
    spans = launch_spans.of(run) or []
    setup_s = run.get("end_to_end", {}).get("setup_s")
    if setup_s is None or "started" not in run.get("spec", {}):
        return None
    window = run["spec"]["started"] + setup_s
    compiles = [s for s in launch_spans.named(
        spans, "xla.compile", workers=True)
        if s["rank"] == 0 and s["end"] <= window]
    if not compiles:
        return None
    return sum(s["end"] - s["start"] for s in compiles)
