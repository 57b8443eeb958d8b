"""The longest a burst waited for the replica's event loop in the window
(engine recorder ``pump_lag``): a stall of that loop shows here and not in
the engine thread's own figures."""


def read(run):
    v = run.get("engine", {}).get("pump_lag_max_s")
    return None if v is None else v * 1e3
