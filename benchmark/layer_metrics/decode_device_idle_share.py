"""Share of the traced window in which no operation ran on the device,
mean over the chips. One reader per end-to-end metric it moves."""

from benchmark.lib.trace import idle_share


def read(run):
    return idle_share(run.get("trace"))
