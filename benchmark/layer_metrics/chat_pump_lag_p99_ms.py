"""How long a burst waited between the engine thread's hand-over and the
replica's event loop picking it up, 99th percentile over the window's bursts
(engine recorder ``pump_lag``). What lies beyond the replica (transport,
proxy write, client) is not in it."""


def read(run):
    v = run.get("engine", {}).get("pump_lag_p99_s")
    return None if v is None else v * 1e3
