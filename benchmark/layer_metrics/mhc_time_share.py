"""Share of the device's busy time under the step's hyper-connections
(``reduced["by_scope"]``: ``jit_steps/hyper_mix``, forward, recomputed and
backward: the norm over the stream's rows, ``phi``'s product, Sinkhorn's
iterations, the mix into every half layer's branch and the mix of its result
back into the rows, the stream's start and its sum at the end;
``ray_tpu/ops/hyper.py``). The prediction module's own two lie under its
scope (``mtp_time_share``). A program whose stream is one row has no such
scope.

The scope is the widened stream's, which only a program that can build the
xingchen_xing4 family's config has: this file asks the family whether the
checkout's does, as the cell is loaded, so that a checkout that cannot train
the cell fails before it starts a trainer."""

from benchmark.lib import scope_share, spec

spec.load_family("xingchen_xing4", spec.root_of(__file__)).require_program()


def read(run):
    return scope_share.share(run, "hyper_mix")
