"""Share of the traced steps in which a collective runs on the device
and no compute does (rank 0's chip)."""


def read(run):
    trace = run.get("trace")
    if not trace or run.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
