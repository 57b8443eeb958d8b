"""Seconds of tear-down by the program's own spans: ``serve_shutdown``
(where the cell serves) plus ``shutdown``, which returns when every process
the runtime spawned has been seen gone."""

from benchmark.lib import lifecycle_record


def read(run):
    down = lifecycle_record.span_s("shutdown")
    if down is None:
        return None
    return down + (lifecycle_record.span_s("serve_shutdown") or 0.0)
