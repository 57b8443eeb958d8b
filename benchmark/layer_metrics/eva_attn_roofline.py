"""EVA attention's kernels' share of their roofline: every named call in the
traced stretch (four a layer and step: ``fwd``, ``dq``, ``dkv``, ``dsum``)
costed by its VISIBLE pairs alone (``benchmark/kernels/eva_attn.py``: the
windows' causal halves and the summary blocks below the diagonal) over the
published peak, over the time the calls took. Compute-bound at the cell's
sequence length. The masked half of a diagonal tile and a summary tile's
narrow product (128 summaries against 1,024 rows) are time the calls take
and not work the model needs: they hold this share under what a full causal
flash call reaches."""

from benchmark.lib import spec, trace

spec.load_family("multibyte_eva", spec.root_of(__file__)).require_program()


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    if not t:
        return None
    share = trace.kernel_roofline(t, "eva_attn", run["device"]["kind"])
    return 100.0 * share["share"] if share else None
