"""The part of ``decode_tick_excess_share`` inside the launch itself, the
compiled call through the read of its tokens (device or runtime): the same
excess over the ``decode_launch`` span alone. The rest of the tick's excess
is host phases."""


def read(run):
    e = run.get("engine", {})
    if not e.get("tick_wall_s") or "launch_excess_s" not in e:
        return None
    return 100.0 * e["launch_excess_s"] / e["tick_wall_s"]
