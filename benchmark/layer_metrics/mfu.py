"""Model FLOP/s utilization: the operations forward and backward need per
token (``arithmetic.train_flops_per_token``: matrix products outside the
embedding lookup, routed experts only, causal attention; nothing recomputed
or padded counts) times tokens per second, over chips times the published
peak."""

from benchmark.lib import arithmetic


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    tr = run.get("train", {})
    if not tr.get("span_s"):
        return None
    cell = run["cell"]
    flops = arithmetic.train_flops_per_token(
        cell["family"], cell["config"]["config"], cell["n_layers"], tr["seq"])
    peak = arithmetic.peaks(run["device"]["kind"])["flops"] * run["device"]["count"]
    return 100.0 * flops * tr["tokens"] / tr["span_s"] / peak
