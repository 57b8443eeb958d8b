"""Seconds from the replica's constructor returning to ``serve.run``
returning (``healthy_wait``): the controller's reconcile taking the replica
in, ``wait_healthy``'s poll, the reply."""

from benchmark.lib import lifecycle_record


def read(run):
    return lifecycle_record.span_s("healthy_wait")
