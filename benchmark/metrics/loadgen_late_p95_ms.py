"""How late the load generator ran: 95th percentile of the time between
the engine marking a client's request done and that client's next request
being submitted. A starved generator must not read as a fast server."""
from benchmark import yardstick


def read(run):
    return yardstick.p95_ms(run["samples"]["late_s"])
