"""The one-token S6 update's share of its roofline: the least time the chip
could take for the traced calls of ``ray_tpu/ops/pallas/s6_update.py`` (their
rows' state read once and written once at the published bandwidth, or their
operations at the published peak, whichever is more:
``benchmark/kernels/s6_update.py`` costs each event from its own name) over the
time they took. Nothing where the trace holds no such call."""

from benchmark.lib import trace


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    kernel = t and trace.kernel_roofline(t, "s6_update", run["device"]["kind"])
    return 100.0 * kernel["share"] if kernel else None
