"""Share of the device's busy time under the step's EVA mixer halves,
forward and backward (``reduced["by_scope"]``, ``jit_steps/attn_eva``): the
norm, the three projections and RoPE, the chunk summaries
(``eva_summaries``), the kernels (``eva_attend``) and ``wo``
(``ray_tpu/models/llama.py:eva_half``). The reduction names an operation by
its outermost scope, so the kernels' own share inside it is not read here
(``eva_attn_roofline`` has their time). A program without an EVA mixer has
no such scope."""

from benchmark.lib import spec

spec.load_family("multibyte_eva", spec.root_of(__file__)).require_program()


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    under = t.get("by_scope", {}).get("jit_steps/attn_eva")
    return 100.0 * under / t["busy_s"] if under is not None else None
