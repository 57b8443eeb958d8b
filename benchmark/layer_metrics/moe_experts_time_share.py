"""Share of the device's busy time under the decode program's
``moe_experts`` scope (``reduced["by_scope"]``): the experts' three grouped
products and the activation between them."""


def read(run):
    t = run.get("trace")
    own = t.get("by_scope", {}).get("jit_rt_decode/moe_experts") if t else None
    return 100.0 * own / t["busy_s"] if own and t["busy_s"] else None
