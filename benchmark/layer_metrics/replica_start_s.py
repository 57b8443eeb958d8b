"""Seconds of ``serve.run`` until the replica's constructor had returned:
the ``serve_run`` span less ``healthy_wait`` (controller, proxy, the worker's
boot and the constructor whole: backend, weights, engine)."""

from benchmark.lib import lifecycle_record


def read(run):
    whole = lifecycle_record.span_s("serve_run")
    wait = lifecycle_record.span_s("healthy_wait")
    return None if whole is None or wait is None else whole - wait
