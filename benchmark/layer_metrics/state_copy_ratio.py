"""State bytes a step that the compiled full-bucket decode program moves
(``state_copy_bytes_per_step``: read by a slice, written in place or a new
buffer; ``ray_tpu/util/hlo_copies.py``, off the executable that runs) over
its rows' state bytes: 2.0 is in place, read once and written once. The
widest fused program (largest ``k``) is read; a model without recurrent
layers records no such counter."""


def read(run):
    progs = [p for p in run.get("engine", {}).get("decode_programs", [])
             if p.get("state_bytes") and p["bucket"] > 1]
    if not progs:
        return None
    p = max(progs, key=lambda p: (p["bucket"], p["k"]))
    slots = run["cell"]["traffic"]["app"]["max_slots"]
    return p["state_copy_bytes_per_step"] / (p["state_bytes"] * p["bucket"] / slots)
