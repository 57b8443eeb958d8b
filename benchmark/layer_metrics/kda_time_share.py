"""Share of the device's busy time under the step's KDA layers' mixer
halves, forward and backward (``reduced["by_scope"]``,
``jit_steps/attn_kda``): the norm, the projections and their convolutions
(``kda_conv``), the gates (``kda_gates``), the chunked recurrence
(``kda_scan``), the output norm and gate, ``wo``
(``ray_tpu/models/mixers.py``). The reduction names an operation by its
outermost scope, so the recurrence's own share inside it is not read. A
program without KDA layers has no such scope."""

from benchmark.lib import spec

spec.load_family("moonshot_kimi_linear", spec.root_of(__file__)).require_program()


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    under = t.get("by_scope", {}).get("jit_steps/attn_kda")
    return 100.0 * under / t["busy_s"] if under is not None else None
