"""Share of the traced window in which a chip's core sat in a collective
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute;
their waits included) and so computed nothing, mean over the chips."""


def read(run):
    t = run.get("trace")
    return 100.0 * t["collective_s"] / t["window_s"] if t and t["window_s"] else None
