"""What stalls cost the clients: 1 - (tokens of all whole bursts over their
time) / (median rate of the window's ten segments of bursts). 0 when the
engine delivered evenly; one run in seven lost ~5% to a single stall of
~2.4 s (PR 22; cause unknown)."""

from statistics import median


def read(run):
    c = run.get("client", {})
    if not c.get("segment_rates") or not c.get("burst_span_s"):
        return None
    steady = median(c["segment_rates"])
    return 100.0 * (1.0 - c["burst_tokens"] / c["burst_span_s"] / steady)
