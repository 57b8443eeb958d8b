"""Share of the window the train loop waited for its next batch (train
recorder phase ``data_wait``)."""


def read(run):
    r = run.get("recorder", {})
    return 100.0 * r["data_wait_s"] / r["span_s"] if r.get("span_s") else None
