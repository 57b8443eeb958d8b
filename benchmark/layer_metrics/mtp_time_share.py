"""Share of the device's busy time under the step's prediction module
(``reduced["by_scope"]``: ``jit_steps/mtp``, forward, recomputed and
backward: the second embedding lookup, the projection of the two normed
inputs, the module's whole expert layer with its own hyper-connections and
flash calls, its final norm and its chunked cross entropy;
``ray_tpu/models/moe.py:_mtp``). A program without a module has no such
scope."""

from benchmark.lib import scope_share, spec

spec.load_family("xingchen_xing4", spec.root_of(__file__)).require_program()


def read(run):
    return scope_share.share(run, "mtp")
