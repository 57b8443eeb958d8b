"""``decode_hbm_share`` for a model whose rows hold window rings, one shared
buffer of keys and values that several layers read, and a recurrent state:
what a decode step has to move (every weight once; every live row's state and
convolution tail read once and written once; of every live row's rings the
positions that are live, at most the window; of the shared buffer the live
positions once for each layer that reads them) over what the chip could have
moved in the step's wall at its published bandwidth. Required bytes of live
rows: the full bucket also steps its free slots, reads every ring whole and
the shared buffer up to the furthest row's bound, which is the program's cost
and not the traffic's need.

The family's arithmetic is ``benchmark/families/sambay.py``'s, which only a
program that can build that family's config can run: this file asks the family
whether the checkout's does, as the cell is loaded, so that a checkout that
cannot run the cell fails before it deploys a replica
(``sambay.require_program``)."""

from benchmark.lib import arithmetic, spec
from benchmark.lib.spec import load_reader

spec.load_family("sambay", spec.root_of(__file__)).require_program()
decode_step_ms = load_reader("decode_step_ms")


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    family = run["cell"]["family"]
    step_ms = decode_step_ms(run)
    reqs = run.get("requests")
    if (not step_ms or not reqs or not hasattr(family, "window_bytes_per_row")
            or not run.get("engine", {}).get("decode_wall_s")):
        return None
    hf, n = run["cell"]["config"]["config"], run["cell"]["n_layers"]
    window = hf["sliding_window"]
    steps = sum(t for _, t in reqs)
    # a step at position q attends to q + 1 positions, its own among them
    live = sum(p * t + t * (t + 1) / 2 for p, t in reqs) / steps
    in_window = sum(min(p + j + 1, window) for p, t in reqs
                    for j in range(t)) / steps
    rows = run["engine"]["occupancy"] * run["cell"]["traffic"]["app"]["max_slots"]
    need = (family.weight_bytes(hf, n)
            + rows * 2 * family.state_bytes_per_row(hf, n)
            + rows * in_window * family.window_bytes_per_row(hf, n) / window
            + rows * live * family.cache_bytes_per_position(hf, n)
            * family.kv_readers(hf, n))
    bandwidth = arithmetic.peaks(run["device"]["kind"])["hbm_bytes_s"]
    return 100.0 * need / (step_ms * 1e-3 * bandwidth)
