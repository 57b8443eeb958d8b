"""Share of the device's busy time under a sparse layer's main attention
(``reduced["by_scope"]``: ``jit_steps/attn_sparse``, forward, recomputed and
backward: the four projections, the head norms, the rotation by sections,
the three flash kernels under the choice and the turn of the choice the dkv
kernel reads; ``ray_tpu/models/mixers.py``). The indexer's three parts lie
under scopes of their own (``index_time_share``). A program without the
kind has no such scope.

The scope is the ``sparse`` kind's, which only a program that can build the
sparse_keye family's config has: this file asks the family whether the
checkout's does, as the cell is loaded, so that a checkout that cannot train
the cell fails before it starts a trainer."""

from benchmark.lib import scope_share, spec

spec.load_family("sparse_keye", spec.root_of(__file__)).require_program()


def read(run):
    return scope_share.share(run, "attn_sparse")
