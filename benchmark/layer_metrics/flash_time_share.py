"""Share of the device's busy time spent in the Pallas calls
(``tpu_custom_call`` events of the trace; the flash kernels are the only
ones in the step)."""


def read(run):
    t = run.get("trace")
    return 100.0 * t["kernel_s"] / t["busy_s"] if t and t["busy_s"] else None
