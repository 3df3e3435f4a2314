"""``setup_s`` by the program's own spans, for the readers of its parts
(PR 37): the stretch from the harness's start (``spec["started"]``) to
the window (that + ``setup_s``, as ``xla_compile_s.train`` cuts) on the
wall clock the launch record keeps, and how much of it spans cover."""

from chipbench import trace_reduce


def stretch(run):
    """``(started, window)`` of `run`, or None where it has no
    ``setup_s`` or no start."""
    setup_s = run.get("end_to_end", {}).get("setup_s")
    if setup_s is None or "started" not in run.get("spec", {}):
        return None
    return run["spec"]["started"], run["spec"]["started"] + setup_s


def rank0(spans, *names):
    return [s for s in spans if s["rank"] == 0 and s["name"] in names]


def covered_s(spans, lo, hi):
    """Seconds of ``[lo, hi]`` that some span of `spans` covers: their
    union (nested and overlapping intervals count once), clipped."""
    clipped = [(max(s["start"], lo), min(s["end"], hi)) for s in spans]
    return sum(end - start for start, end in trace_reduce.union(clipped))
