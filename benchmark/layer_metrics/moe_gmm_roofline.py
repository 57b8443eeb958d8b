"""The grouped expert products' share of their roofline: the least time the
chip could take for the traced calls (``benchmark/kernels/moe_gmm.py``: the
larger of operations over peak and bytes over peak bandwidth) over the time
they took. Bandwidth-bound at a decode step's rows, where the bytes are the
experts' weights; an expert no row went to is not read, so the bytes are
scaled by the share of expert slots the program's counters say were touched
in the window's decode launches (the rows' own bytes, under 1% here, with
them)."""

from benchmark.lib import trace


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    share = t and trace.kernel_roofline(t, "moe_gmm", run["device"]["kind"])
    if not share:
        return None
    moe = run.get("engine", {}).get("moe_decode") or {}
    touched = (moe["moe_experts_touched"] / moe["moe_expert_slots"]
               if moe.get("moe_expert_slots") else 1.0)
    return 100.0 * share["share"] * (1.0 if share["compute_bound"] else touched)
