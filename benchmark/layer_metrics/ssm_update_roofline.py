"""The one-token recurrent update's share of its roofline: the least time
the chip could take to read every stepped row's state once and write it
once (and its convolution tail: ``state_bytes_per_row``, twice), at its
published bandwidth, over the time the device spent under
``jit_rt_decode/ssm_update``. The update is bandwidth-bound: 5 operations an
element of a state that crosses HBM twice.

Where the update is a Pallas call, its events carry their own shapes
(``benchmark/kernels/ssm_update.py``) and ``trace.kernel_roofline`` costs
the traced calls exactly. Where it is XLA fusions, the rows stepped in the
traced stretch are the window's (the recorder's ticks: bucket x steps, a
free slot of the full bucket is stepped like a live one) scaled by the
stretch's length: good to the steadiness of a closed loop, a percent or two.
The tail is counted with the state though its scope is ``ssm_conv``: 1.2% of
the bytes, and counting it can only lower the share."""

from benchmark.lib import arithmetic, trace


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    if not t:
        return None
    kernel = trace.kernel_roofline(t, "ssm_update", run["device"]["kind"])
    if kernel:
        return 100.0 * kernel["share"]
    under = t.get("by_scope", {}).get("jit_rt_decode/ssm_update")
    ticks = run.get("engine", {}).get("ticks")
    family = run["cell"]["family"]
    if not under or not ticks or not hasattr(family, "state_bytes_per_row"):
        return None
    row_steps = sum(tick["bucket"] * tick["k"] for tick in ticks)
    traced = row_steps * t["window_s"] / run["seconds"]
    need = traced * 2 * family.state_bytes_per_row(
        run["cell"]["config"]["config"], run["cell"]["n_layers"])
    bandwidth = arithmetic.peaks(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * need / bandwidth / under
