"""Share of the engine's tick wall spent admitting requests: the recorder's
``admission``, ``kv_restore`` and ``prefill`` phases together. On the chip the
``prefill`` phase alone ends when the program is dispatched, and the wait
for its first token is booked to ``admission`` (PR 22), so the three are
read as one. Decode waits meanwhile."""


def read(run):
    e = run.get("engine", {})
    if not e.get("tick_wall_s"):
        return None
    phases = e.get("phase_s", {})
    admit = sum(phases.get(p, 0.0) for p in ("admission", "kv_restore", "prefill"))
    return 100.0 * admit / e["tick_wall_s"]
