"""Of the assignments that landed on an expert held on this chip, the share
dropped beyond the expert's capacity (the train recorder's counters
``moe_dropped`` over ``moe_held``, over the measured window's launches; see
``moe_held_share.py`` for where they are kept). A dropped row's expert adds
nothing for that token: the program's buffers and the reference drop the
same rows."""

from benchmark.lib import launch_record


def read(run):
    r = launch_record.window_sums(run)
    return (100.0 * r.get("moe_dropped", 0) / r["moe_held"]
            if r and r.get("moe_held") else None)
