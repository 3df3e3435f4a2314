"""Device time of the decode-chunk XLA module in the traced stretch over
the decode steps ``engine.stats`` counted there (a chunk is up to 16)."""

MODULE = "jit_decode_chunk"


def read(run):
    trace, stats = run.get("trace"), run.get("engine_stats", {})
    if not trace or "trace_end" not in stats:
        return None
    steps = stats["trace_end"]["steps"] - stats["trace_start"]["steps"]
    seconds = sum(s for name, s in trace["modules_s"].items()
                  if name.startswith(MODULE))
    if steps <= 0 or seconds <= 0:
        return None
    return 1e3 * seconds / steps
