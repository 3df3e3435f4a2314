"""How late the generator ran: sent - due per request, 95th percentile.
A starved generator must not be read as a fast server."""


def read(run):
    return run.get("client", {}).get("late_p95_ms")
