"""Share of the window's requests that met both limits of the traffic file
(first token from the due instant, mean gap between tokens). A failed
request misses."""


def read(run):
    c = run.get("client", {})
    if "slo_met" not in c or not c.get("attempted"):
        return None
    return 100.0 * c["slo_met"] / c["attempted"]
