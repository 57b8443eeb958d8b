"""Share of the device's busy time under the step's window layers' attention
halves (``reduced["by_scope"]``: ``jit_steps/attn_window``, forward and
backward: the norms, the five projections, the rotation, the banded flash
calls, the gate; ``ray_tpu/models/moe.py``'s patterned walk). A program
without window layers has no such scope."""


def read(run):
    t = run.get("trace")
    own = t and t.get("by_scope", {}).get("jit_steps/attn_window")
    return 100.0 * own / t["busy_s"] if own and t.get("busy_s") else None
