"""What the compiled train step needs of one device's memory at its peak, by
the compiler's own account, in GiB (the train recorder's ``step_memory``,
``peak_bytes``: ``memory_analysis()`` of the executable that runs, read at
the launch that compiled it and kept with the run's launches by the
trainer's process: ``benchmark/lib/launch_record.py``). ``peak_hbm_gib``
holds live arrays; a launch's temporaries show only here. A program that
keeps no such record says nothing."""

from benchmark.lib import launch_record


def read(run):
    memory = (launch_record.totals() or {}).get("step_memory")
    peak = memory.get("peak_bytes") if memory else None
    return peak / 2 ** 30 if peak else None
