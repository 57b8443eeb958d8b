"""Share of the device's busy time under ``index_select`` alone
(``reduced["by_scope"]``): the exact ``topk``-th largest of every row's
causal past, sixteen passes of three counts over a block of scores, and the
int8 mask (``ray_tpu/ops/sparse_index.threshold``, ``choose``). What a
threshold kernel that holds a block in VMEM would take out."""

from benchmark.lib import scope_share, spec

spec.load_family("sparse_keye", spec.root_of(__file__)).require_program()


def read(run):
    return scope_share.share(run, "index_select")
