"""Share of the engine's tick wall spent on admission's host bookkeeping
alone: reap of cancellations, queue pop, slot bookkeeping, emit of the first
token (the recorder's ``admission`` span). A recorder that splits
``decode_step`` into parts (``decode_parts_s``) also ends ``prefill`` at the
read of the first token; an older one books that wait here, and is not read."""


def read(run):
    e = run.get("engine", {})
    if not e.get("tick_wall_s") or "decode_parts_s" not in e:
        return None
    return 100.0 * e.get("phase_s", {}).get("admission", 0.0) / e["tick_wall_s"]
