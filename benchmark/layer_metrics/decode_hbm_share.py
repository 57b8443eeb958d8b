"""What a decode step has to read (every weight once, and the keys and
values of the live positions) over what the chip could have read in the
step's wall at its published bandwidth. The wall is the recorder's fenced
``decode_step``: prefill and decode programs are all ``jit_run`` in a trace."""

from benchmark.lib import arithmetic
from benchmark.lib.spec import load_reader

decode_step_ms = load_reader("decode_step_ms")


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    step_ms = decode_step_ms(run)
    reqs = run.get("requests")
    if not step_ms or not reqs or not run.get("engine", {}).get("decode_wall_s"):
        return None
    family = run["cell"]["family"]
    hf, n = run["cell"]["config"]["config"], run["cell"]["n_layers"]
    # mean live context of a decoding row: a request of prompt p and n tokens
    # holds p, p+1, .. p+n-1 positions over its n steps
    steps = sum(t for _, t in reqs)
    live = sum(p * t + t * (t - 1) / 2 for p, t in reqs) / steps
    rows = run["engine"]["occupancy"] * run["cell"]["traffic"]["app"]["max_slots"]
    need = (arithmetic.weight_bytes(family, hf, n)
            + rows * live * family.cache_bytes_per_position(hf, n))
    bandwidth = arithmetic.peaks(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * need / (step_ms * 1e-3 * bandwidth)
