"""Share of the device's busy time under the indexer's three scopes
(``reduced["by_scope"]``: ``jit_steps/index_scores``, the indexer's
projections and its scores for the choice; ``index_select``, the exact
threshold of every row and the mask; ``index_loss``, the loss's pass over
the blocks, which rebuilds the scores and the main attention's weights and
forms the indexer's gradients; ``ray_tpu/ops/sparse_index.py``). None where
the program names none of them."""

from benchmark.lib import scope_share, spec

spec.load_family("sparse_keye", spec.root_of(__file__)).require_program()


def read(run):
    parts = [scope_share.share(run, scope)
             for scope in ("index_scores", "index_select", "index_loss")]
    return sum(p for p in parts if p is not None) \
        if any(p is not None for p in parts) else None
