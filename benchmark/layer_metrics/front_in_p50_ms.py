"""What the front costs a request on the way in: HTTP proxy receipt to
engine submit (routing, handle, replica entry, executor hand-off), per
request, 50th percentile over requests that finished in the window (the
proxy's t_ingress against the engine's t_submit, one host's clock)."""


def read(run):
    v = run.get("engine", {}).get("front_in_p50_s")
    return None if v is None else v * 1e3
