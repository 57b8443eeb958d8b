"""Share of the device's busy time under the step's update
(``reduced["by_scope"]``: ``jit_steps/optimizer``: the gradients' global norm
and clip, AdamW's moments and update, the parameters' move, a family's
buffers; ``ray_tpu/parallel/train_step.py``). It reads and writes every
parameter and both moments once a step whatever the sequence, so it weighs
most where a step's tokens are few for its parameters. A program that names
no such scope has none."""

from benchmark.lib import scope_share


def read(run):
    return scope_share.share(run, "optimizer")
