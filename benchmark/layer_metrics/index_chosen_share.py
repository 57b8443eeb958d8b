"""Of the causal (query, key) pairs of the step's sparse layers, the share
their indexers chose (the train recorder's counters ``index_pairs_chosen``
over ``index_pairs_live``, over the measured window's launches, warm-up left
out, as the trainer's process kept them: ``benchmark/lib/launch_record.py``).
At s 16,384 and topk 2,048 a choice of exactly topk keeps 23.4% (31.5M of
134.2M a layer); ties at the threshold add to it."""

from benchmark.lib import launch_record, spec

spec.load_family("sparse_keye", spec.root_of(__file__)).require_program()


def read(run):
    r = launch_record.window_sums(run)
    return (100.0 * r.get("index_pairs_chosen", 0) / r["index_pairs_live"]
            if r and r.get("index_pairs_live") else None)
