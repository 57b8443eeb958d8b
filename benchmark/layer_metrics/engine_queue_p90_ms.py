"""How long a request waited for a slot: engine submit to popped for
admission, 90th percentile over requests that finished in the window
(engine recorder queue_s; first-token time less this is the prefill and
the launch it waited for)."""


def read(run):
    v = run.get("engine", {}).get("queue_p90_s")
    return None if v is None else v * 1e3
