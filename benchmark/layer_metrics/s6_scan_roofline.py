"""The prefill's S6 scan's share of its roofline: the least time the chip
could take for the scans of the prefills in the traced stretch (the larger of
their operations over peak and their bytes over peak bandwidth:
``benchmark/kernels/s6_scan.py``, from the prompt's length and the family's
sizes) over the time the device spent under ``jit_rt_prefill/ssm_scan``. The
prefills of the stretch are the window's (the clients' requests by prompt
length) scaled by the stretch's length, as ``ssd_prefill_roofline`` takes them:
a 4 s stretch holds a dozen, so this reads to a tenth, not to a percent.
Reported where the program's prefill counts its layer-tokens
(``prefill_layer_tokens_whole`` in the recorder's window), which only a
program with this scan does."""

from benchmark.lib import arithmetic, spec

s6_scan = spec.load_kernels(spec.root_of(__file__))["s6_scan"]


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    under = t and t.get("by_scope", {}).get("jit_rt_prefill/ssm_scan")
    reqs = run.get("requests")
    layout = run.get("engine", {}).get("state_layout", {})
    if (not under or not reqs or not layout.get("kinds")
            or not run["engine"].get("prefill_layer_tokens_whole")):
        return None
    layers = layout["kinds"]["mamba"]
    flops = nbytes = 0.0
    for prompt, _ in reqs:
        f, b = s6_scan.scan_cost(prompt, run["cell"]["config"]["config"])
        flops, nbytes = flops + layers * f, nbytes + layers * b
    peak = arithmetic.peaks(run["device"]["kind"])
    least = max(flops / peak["flops"], nbytes / peak["hbm_bytes_s"])
    return 100.0 * least * (t["window_s"] / run["seconds"]) / under
