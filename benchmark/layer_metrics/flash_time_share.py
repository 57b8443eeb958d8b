"""Share of the device's busy time spent in the flash kernels' calls (the
``tpu_custom_call`` events of the trace that ``benchmark/kernels/flash.py``
knows; they are the only Pallas calls in the step)."""


def read(run):
    t = run.get("trace")
    flash = t["kernels"].get("flash") if t else None
    return 100.0 * flash["seconds"] / t["busy_s"] if flash and t["busy_s"] else None
