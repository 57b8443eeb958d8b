"""Share of the device's busy time under the step's expert feed-forward:
the router, the rows' way into the capacity buffers, the held experts'
products, the way back and the shared expert (``reduced["by_scope"]``:
``jit_steps/moe_router`` + ``moe_dispatch`` + ``moe_experts`` +
``moe_combine`` + ``moe_shared``, forward and backward). A step without
expert layers has none of them."""

SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "moe_shared")


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    scopes = t.get("by_scope", {})
    found = [scopes[k] for k in ("jit_steps/" + s for s in SCOPES) if k in scopes]
    return 100.0 * sum(found) / t["busy_s"] if found else None
