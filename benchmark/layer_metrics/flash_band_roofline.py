"""The flash kernels' share of their roofline with every call costed by the
pairs its mask leaves alive (``benchmark/kernels/flash_band.py``: the band of
a window layer, the half square of a full one) over the published peak, over
the time the calls took. Compute-bound at the cell's sequence length.
``flash_roofline`` costs every call as a full causal square and would read a
third too high on a banded call at s 8192."""

from benchmark.lib import trace


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    if not t:
        return None
    share = trace.kernel_roofline(t, "flash_band", run["device"]["kind"])
    return 100.0 * share["share"] if share else None
