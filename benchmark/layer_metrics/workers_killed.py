"""Processes of the run that ended by SIGKILL (rows with ``ended_by:
sigkill``): each one sat out the request and the SIGTERM before it."""

from benchmark.lib import lifecycle_record


def read(run):
    rec = lifecycle_record.shutdown()
    return rec and sum(r["ended_by"] == "sigkill" for r in rec["rows"])
