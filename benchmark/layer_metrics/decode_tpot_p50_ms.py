"""Median time per output token inside the replica, over requests that
finished in the window (engine recorder)."""


def read(run):
    e = run.get("engine", {})
    return e["tpot_p50_s"] * 1e3 if e.get("window_completed") else None
