"""Share of the device's busy time under the step's loss
(``reduced["by_scope"]``: ``jit_steps/loss_head``, forward and backward: the
final norm, the head's cast, the chunked cross entropy's loop with its
recomputed logits, a multi-head model's heads;
``ray_tpu/models/llama.py:lm_loss``, ``models/moe.py:loss_and_stats``). A
program that names no such scope has none."""

from benchmark.lib import scope_share


def read(run):
    return scope_share.share(run, "loss_head")
