"""The prefill's chunked scan's share of its roofline: the least time the
chip could take for the scans of the prefills in the traced stretch (the
larger of their operations over peak and their bytes over peak bandwidth:
``benchmark/kernels/ssd_scan.py``, from the prompt's length and the
published sizes) over the time the device spent under
``jit_rt_prefill/ssm_scan``. The prefills of the stretch are the window's
(the clients' requests by prompt length) scaled by the stretch's length; a
4 s stretch holds ~20 of them, so this reads to a fifth, not to a percent.
Reported where the program counts scan chunks (``ssm_scan_chunks`` in the
recorder's window): a model without recurrent layers has no such scope."""

from benchmark.lib import arithmetic, spec

ssd_scan = spec.load_kernels(spec.root_of(__file__))["ssd_scan"]


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    under = t and t.get("by_scope", {}).get("jit_rt_prefill/ssm_scan")
    reqs = run.get("requests")
    if not under or not reqs or not run.get("engine", {}).get("ssm_scan_chunks"):
        return None
    hf, n = run["cell"]["config"]["config"], run["cell"]["n_layers"]
    layers = hf["layer_types"][:n].count("mamba")
    flops = nbytes = 0.0
    for prompt, _ in reqs:
        f, b = ssd_scan.scan_cost(prompt, hf)
        flops, nbytes = flops + layers * f, nbytes + layers * b
    peak = arithmetic.peaks(run["device"]["kind"])
    least = max(flops / peak["flops"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least * (t["window_s"] / run["seconds"]) / under
