"""Share of the device's busy time under the step's full attention halves
(``reduced["by_scope"]``: ``jit_steps/attn_full``, forward and backward: the
norm, the q, k, v projections, the rotation, the flash calls or the dense
product, the gate, ``wo``, the residual sum; ``ray_tpu/models/llama.py``'s and
``models/moe.py``'s training blocks and the patterned walk's ``full`` layers).
The flash kernels' own time lies inside it. A program whose training blocks
name no such scope has none."""

from benchmark.lib import scope_share


def read(run):
    return scope_share.share(run, "attn_full")
