"""Seconds the chip's worker spent in the compiler or fetching from the
persistent cache before the window opened (``CompileCounter``)."""


def read(run):
    return run.get("compile_s_at_window")
