"""Host time between two decode launches as the engine loop sees it: one
``step_many`` returned to the next entered (token delivery, the recorder,
reap, admission), median per launch (``tick_gap_p50_s``). Staging and
bookkeeping lie inside ``step_many``; the device's whole gap is this plus
those, which the trace's ``idle_gaps`` split by span."""


def read(run):
    e = run.get("engine", {})
    return e["tick_gap_p50_s"] * 1e3 if e.get("decode_wall_s") else None
