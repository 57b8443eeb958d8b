"""What stalls of the engine thread cost, by its own clock: over the
window's ticks that launched a decode, the wall beyond twice the median
tick of the same bucket and stride, as a share of the tick wall. Beside
``decode_stall_share`` (the clients' clock): a stall the clients saw and
this does not lies after the engine thread."""


def read(run):
    e = run.get("engine", {})
    if not e.get("tick_wall_s") or "tick_excess_s" not in e:
        return None
    return 100.0 * e["tick_excess_s"] / e["tick_wall_s"]
