"""Seconds of ``ray_tpu.init()`` by the program's own ``init`` span: the head,
the node and the driver's connection (what the ``setup:`` note's ``runtime``
times from outside, less the interpreter's start and the imports)."""

from benchmark.lib import lifecycle_record


def read(run):
    return lifecycle_record.span_s("init")
