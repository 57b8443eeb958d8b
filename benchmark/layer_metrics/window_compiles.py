"""Programs the chip's worker asked the compiler or its cache for between
the window's start and its end. Should be 0: what was not warmed in set-up
shows here."""


def read(run):
    return run.get("window_compiles")
