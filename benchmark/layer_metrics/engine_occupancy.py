"""Active slots over all slots, weighted by decode wall (engine recorder)."""


def read(run):
    e = run.get("engine", {})
    return 100.0 * e["occupancy"] if e.get("decode_wall_s") else None
