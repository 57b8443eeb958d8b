"""The hyper-connections' share of HBM's pace: the bytes the least passes
over the widened stream move for the traced stretch's tokens (the family's
arithmetic, ``xingchen_xing4.hyper_stream_bytes_per_token``: forward ``(3 n
+ 2) d`` and backward ``(5 n + 3) d`` elements a token and half layer,
whatever implements them; nothing that remat runs again, no coefficient)
over the published bytes a second, over the time under the scope
``hyper_mix`` (``reduced["by_scope"]``). Bandwidth-bound by construction:
the mixes multiply a token's rows by a 4 x 4 matrix.

The stretch's tokens are counted by its flash calls: three a layer and step
(``fwd``, ``dq``, ``dkv``; the remat blocks keep the forward's results), the
trunk's layers and the module's, each step ``batch x seq`` tokens; a stretch
that cuts a step counts the part it holds. None off the chip, without a
trace, or where the step ran no flash kernel at two widths."""

from benchmark.lib import arithmetic, spec

_FAMILY = spec.load_family("xingchen_xing4", spec.root_of(__file__))
_FAMILY.require_program()


def read(run):
    if run["device"]["platform"] != "tpu":
        return None  # a rehearsal off the chip has no device number
    t = run.get("trace")
    own = t and t.get("by_scope", {}).get("jit_steps/hyper_mix")
    calls = t and t.get("kernels", {}).get("flash_mla", {}).get("calls")
    if not own or not calls:
        return None
    cell, tr = run["cell"], run["train"]
    hf = cell["config"]["config"]
    layers = cell["n_layers"] + hf["num_nextn_predict_layers"]
    tokens = calls / (3.0 * layers) * tr["batch"] * tr["seq"]
    least = (tokens * _FAMILY.hyper_stream_bytes_per_token(hf, cell["n_layers"])
             / arithmetic.peaks(run["device"]["kind"])["hbm_bytes_s"])
    return 100.0 * least / own
