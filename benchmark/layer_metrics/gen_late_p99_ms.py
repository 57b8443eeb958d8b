"""How late the load generator ran: send instant minus due instant, 99th
percentile over the window's requests. A starved generator must not be read
as a fast server."""

from benchmark.lib.loadgen import client_percentile


def read(run):
    return client_percentile(run, "late_ms", 99)
