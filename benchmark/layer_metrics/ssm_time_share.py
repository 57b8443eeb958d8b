"""Share of the device's busy time under the decode program's state-space
scopes (``reduced["by_scope"]``): ``jit_rt_decode/ssm_proj`` (a mamba
layer's two projections and its gated norm), ``/ssm_conv`` (the
convolution, its tail read and written, the split and ``dt``) and
``/ssm_update`` (the recurrence: the rows' state read and written).

The scopes are those of ``ray_tpu/models/hybrid.py``, which only a program
that can build the ssm_hybrid family's config has: this file asks the
family whether the checkout's does, as the cell is loaded, so that a
checkout that cannot run the cell fails before it deploys a replica
(``ssm_hybrid.require_program``)."""

from benchmark.lib import spec

spec.load_family("ssm_hybrid", spec.root_of(__file__)).require_program()


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    own = sum(s for scope, s in t.get("by_scope", {}).items()
              if scope.startswith("jit_rt_decode/ssm_"))
    return 100.0 * own / t["busy_s"] if own else None
