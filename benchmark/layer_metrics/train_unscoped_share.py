"""Share of the device's busy time that lies under no scope of the step
(``reduced["by_scope"]``: ``jit_steps/other``): arithmetic and copies whose
``op_name`` holds no ``jax.named_scope``, which is what a reader of the
program cannot place. ``other/collective`` is not in it (a collective that
completes no scoped product: ``collective_exposed_share`` has the
collectives). None where the trace holds no train step at all; 0.0 where the
step ran and every operation of it is named."""


def read(run):
    t = run.get("trace")
    if not t or not t.get("busy_s"):
        return None
    scopes = t.get("by_scope", {})
    if not any(k.startswith("jit_steps/") for k in scopes):
        return None
    return 100.0 * scopes.get("jit_steps/other", 0.0) / t["busy_s"]
