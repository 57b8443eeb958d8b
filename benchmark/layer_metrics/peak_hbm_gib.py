"""Peak device memory of the fullest chip (``memory_stats``), in GiB."""


def read(run):
    peak = run["device"].get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
