"""Wall of one fused decode step at the full bucket: the recorder's
``decode_step`` phase (launch to host read of the tokens) over the steps of
the launch, over the window's full-bucket ticks."""


def read(run):
    ticks = [t for t in run.get("engine", {}).get("ticks", [])
             if t["bucket"] > 1 and t["k"] > 0 and t["decode_step_s"] > 0]
    steps = sum(t["k"] for t in ticks)
    return 1e3 * sum(t["decode_step_s"] for t in ticks) / steps if steps else None
