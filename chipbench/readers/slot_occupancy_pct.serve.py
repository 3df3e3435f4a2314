"""``engine.stats``: active slot-steps over all slot-steps of the decode
chunks that ran inside the window."""


def read(run):
    stats = run.get("engine_stats")
    if not stats:
        return None
    total = (stats["end"]["total_slot_steps"]
             - stats["start"]["total_slot_steps"])
    if total <= 0:
        return None
    return 100.0 * (stats["end"]["active_slot_steps"]
                    - stats["start"]["active_slot_steps"]) / total
