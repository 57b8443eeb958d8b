"""The flash kernels' share of their roofline at two head widths: every
named call costed by the pairs its mask leaves alive and by its own widths
(``benchmark/kernels/flash_mla.py``: scores at 192, values at 128) over the
published peak, over the time the calls took. Compute-bound at the cell's
sequence length. The second, half-empty pass the MXU makes over a 192-wide
contraction is time the calls take and not work the model needs: it holds
this share under what a call of one width reaches."""

from benchmark.lib import spec, trace

spec.load_family("moonshot_kimi_linear", spec.root_of(__file__)).require_program()


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    if not t:
        return None
    share = trace.kernel_roofline(t, "flash_mla", run["device"]["kind"])
    return 100.0 * share["share"] if share else None
