"""First streamed token minus the request's due instant, median
over the window's requests. Not judged: at 2 requests a second a window holds
~100 requests, and this statistic spreads by more than any bound allows
(PERF.md, section 2); it is reported so that a change can be read against it."""

from benchmark.lib.loadgen import client_percentile


def read(run):
    return client_percentile(run, "ttft_ms", 50)
