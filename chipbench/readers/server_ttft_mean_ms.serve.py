"""``server_first_token_seconds`` from ``GET /metrics`` (admission to
first token, always on): sum over count of the requests first answered
inside the window. The mean and not a percentile: the histogram's
buckets (0.1, 0.25, 0.5, 1 s ...) are too coarse to interpolate in. The
client's mean beside it (``ttft_mean_ms`` in the run's notes) differs by
HTTP, the stream and the generator."""

import re

NAME = "server_first_token_seconds"


def _read(text, suffix):
    found = re.search(rf"^{NAME}_{suffix} (\S+)$", text, re.M)
    return float(found.group(1)) if found else 0.0


def read(run):
    if "metrics_text" not in run:
        return None
    before, after = run["metrics_text"]
    count = _read(after, "count") - _read(before, "count")
    if count <= 0:
        return None
    return 1e3 * (_read(after, "sum") - _read(before, "sum")) / count
