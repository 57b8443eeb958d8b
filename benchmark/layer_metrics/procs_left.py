"""Processes the runtime spawned that were still alive when
``ray_tpu.shutdown()`` returned (``procs_alive_at_return``). One left behind
holds the next run's chips: it moves, or fails, the next ``setup_s``."""

from benchmark.lib import lifecycle_record


def read(run):
    rec = lifecycle_record.shutdown()
    return rec and rec["procs_alive_at_return"]
