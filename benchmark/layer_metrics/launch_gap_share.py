"""Share of the window between the end of one launch's dispatch and the
next launch's start with nothing queued (train recorder ``gap_s``)."""


def read(run):
    r = run.get("recorder", {})
    return 100.0 * r["launch_gap_s"] / r["span_s"] if r.get("span_s") else None
