"""95th percentile of how late the load generator sent a request after
it was due, in ms.  The generator shares the event loop with the service,
so a high reading means the host was busy serving, not that arrivals
thinned: every request's latency still counts from when it was due."""


def read(cell):
    return cell.host.get("lag_p95_ms")
