"""The flash kernels' share of their roofline under a learned choice: every
named call costed by the pairs the choice KEEPS (``benchmark/kernels/
flash_select.py``: ``min(p + 1, topk)`` a query, at the call's one width)
over the published peak, over the time the calls took. The kernels walk every
causal tile and mask, so at s 16,384 and topk 2,048 this cannot read over
23%: the distance to 100 is what skipping tiles no row chose from, or
gathering, would be worth, and the number reads the same work whatever
implements the choice."""

from benchmark.lib import spec, trace

spec.load_family("sparse_keye", spec.root_of(__file__)).require_program()


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    if not t:
        return None
    share = trace.kernel_roofline(t, "flash_select", run["device"]["kind"])
    return 100.0 * share["share"] if share else None
