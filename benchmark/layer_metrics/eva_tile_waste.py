"""Score tiles the step's EVA attention visits over the tiles that hold a
visible pair, one head, at the implementation's own block sizes (the train
recorder's ``eva_plan``, ``tiles_visited`` over ``tiles_needed``, as the
trainer's process kept it with the run's launches:
``benchmark/lib/launch_record.py``). 1.0 where every tile visited is needed
(the kernels walk a list of the needed tiles); the dense form visits the
whole ``[s, s + s / chunk]`` rectangle. A tile the diagonal crosses counts as
one needed and one visited though half of it is masked: that half is in
``eva_attn_roofline``. A program without an EVA mixer notes no plan."""

from benchmark.lib import launch_record, spec

spec.load_family("multibyte_eva", spec.root_of(__file__)).require_program()


def read(run):
    plan = (launch_record.totals() or {}).get("eva_plan")
    if not plan or not plan.get("tiles_needed"):
        return None
    return plan["tiles_visited"] / plan["tiles_needed"]
