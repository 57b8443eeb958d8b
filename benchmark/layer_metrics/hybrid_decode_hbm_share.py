"""``decode_hbm_share`` for a model whose rows hold a recurrent state: what
a decode step has to move (every weight once, the keys and values of the
live positions of the attention layers, and every live row's state and
convolution tail read once and written once: the family's
``state_bytes_per_row``, which ``decode_hbm_share``'s arithmetic has no term
for) over what the chip could have moved in the step's wall at its
published bandwidth. Required bytes of live rows: the full bucket also steps
its free slots, which is the program's cost and not the traffic's need."""

from benchmark.lib import arithmetic
from benchmark.lib.spec import load_reader

decode_step_ms = load_reader("decode_step_ms")


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    family = run["cell"]["family"]
    step_ms = decode_step_ms(run)
    reqs = run.get("requests")
    if (not step_ms or not reqs or not hasattr(family, "state_bytes_per_row")
            or not run.get("engine", {}).get("decode_wall_s")):
        return None
    hf, n = run["cell"]["config"]["config"], run["cell"]["n_layers"]
    steps = sum(t for _, t in reqs)
    live = sum(p * t + t * (t - 1) / 2 for p, t in reqs) / steps
    rows = run["engine"]["occupancy"] * run["cell"]["traffic"]["app"]["max_slots"]
    need = (family.weight_bytes(hf, n)
            + rows * live * family.cache_bytes_per_position(hf, n)
            + rows * 2 * family.state_bytes_per_row(hf, n))
    bandwidth = arithmetic.peaks(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * need / (step_ms * 1e-3 * bandwidth)
