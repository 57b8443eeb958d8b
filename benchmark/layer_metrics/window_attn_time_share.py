"""Share of the device's busy time under the decode program's window
attention (``reduced["by_scope"]``: ``jit_rt_decode/attn_window``, a window
layer's projections, its ring's write and whole read, the differential
combination and the output projection; ``ray_tpu/models/sambay.py``). A
program without window layers has no such scope."""


def read(run):
    t = run.get("trace")
    own = t and t.get("by_scope", {}).get("jit_rt_decode/attn_window")
    return 100.0 * own / t["busy_s"] if own and t.get("busy_s") else None
