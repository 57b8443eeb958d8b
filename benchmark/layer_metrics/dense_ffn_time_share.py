"""Share of the device's busy time under the step's dense feed-forward
halves (``reduced["by_scope"]``: ``jit_steps/mlp``, forward and backward: the
norm, the gate, up and down products, SiLU, the residual sum;
``ray_tpu/models/llama.py:ffn_half`` as a training block calls it). The
routed experts' feed-forward is ``moe_ffn_time_share``'s. A program whose
training blocks name no such scope has none."""

from benchmark.lib import scope_share


def read(run):
    return scope_share.share(run, "mlp")
