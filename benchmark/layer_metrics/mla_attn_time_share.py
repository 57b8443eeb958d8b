"""Share of the device's busy time under the step's MLA layers' mixer halves
(``reduced["by_scope"]``: ``jit_steps/attn_mla``, forward and backward: the
norm, the latent's projections, the flash calls at two widths, the output
projection; ``ray_tpu/models/moe.py``'s patterned walk). A program without
MLA layers has no such scope."""

from benchmark.lib import spec

spec.load_family("moonshot_kimi_linear", spec.root_of(__file__)).require_program()


def read(run):
    t = run.get("trace")
    own = t and t.get("by_scope", {}).get("jit_steps/attn_mla")
    return 100.0 * own / t["busy_s"] if own and t.get("busy_s") else None
