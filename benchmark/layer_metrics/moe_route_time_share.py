"""Share of the device's busy time the decode program spends round the
experts and not in them: the ``moe_router`` (norm, router product, softmax,
top-k), ``moe_dispatch`` (sort by expert, group sizes, row gather) and
``moe_combine`` (gather back, gate-weighted sum, residual) scopes of
``reduced["by_scope"]``."""

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(run):
    t = run.get("trace")
    if not t or not t["busy_s"]:
        return None
    own = [t.get("by_scope", {}).get("jit_rt_decode/" + s) for s in SCOPES]
    if all(v is None for v in own):
        return None  # a program without these scopes
    return 100.0 * sum(v or 0.0 for v in own) / t["busy_s"]
