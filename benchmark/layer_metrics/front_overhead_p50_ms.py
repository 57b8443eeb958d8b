"""What the path round the engine costs a first token: the clients' median
time from send to first token, less the engine recorder's median from
submit to first token, over the window (proxy, handle, replica, stream)."""

from statistics import median


def read(run):
    sent = run.get("client", {}).get("send_ttft_ms")
    engine = run.get("engine", {}).get("ttft_p50_s")
    if not sent or engine is None:
        return None
    return median(sent) - engine * 1e3
