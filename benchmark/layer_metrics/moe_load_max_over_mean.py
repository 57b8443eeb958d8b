"""The busiest expert's rows in any one layer of any decode step of the
window, over the mean rows an expert got (assignments over expert slots):
how uneven the routing was at its worst."""


def read(run):
    moe = run.get("engine", {}).get("moe_decode") or {}
    if not moe.get("moe_assignments") or not moe.get("moe_expert_slots"):
        return None
    mean = moe["moe_assignments"] / moe["moe_expert_slots"]
    return moe["moe_max_expert_rows"] / mean
