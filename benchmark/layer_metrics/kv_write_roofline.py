"""The new keys' and values' write's share of its roofline: the least time
the chip could take for the traced calls of ``ray_tpu/ops/pallas/kv_write.py``
(a tile a row and buffer read and written back at the published bandwidth:
``benchmark/kernels/kv_write.py`` costs each event from its own name) over the
time they took. The call moves 40 KB a row and buffer, so its grid steps and
not the bytes set its pace: a low share is what the design costs, and the
scope's seconds say whether it matters. Nothing where the trace holds no
such call."""

from benchmark.lib import trace


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    kernel = t and trace.kernel_roofline(t, "kv_write", run["device"]["kind"])
    return 100.0 * kernel["share"] if kernel else None
