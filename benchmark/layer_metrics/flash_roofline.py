"""The flash kernels' share of their roofline: operations the traced calls
need (from each event's own shapes) over the published peak, over the time
the calls took. Compute-bound at the cells' sequence lengths."""

from benchmark.lib import trace


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    if not t:
        return None
    share = trace.kernel_roofline(t, "flash", run["device"]["kind"])
    return 100.0 * share["share"] if share else None
