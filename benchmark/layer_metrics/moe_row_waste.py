"""Rows the experts' grouped products ran over the (token, expert)
assignments that were routed, over the window's decode launches (the engine
recorder's ``moe_decode`` counters): 1.0 is no padding; the one-hot
drop-free form read E (64) here.

The counters are the served expert path's, which only a program that can
build the olmoe family's config has: this file asks the family whether the
checkout's does, as the cell is loaded, so that a checkout that cannot run
the cell fails before it deploys a replica (``olmoe.require_program``)."""

from benchmark.lib import spec

spec.load_family("olmoe", spec.root_of(__file__)).require_program()


def read(run):
    moe = run.get("engine", {}).get("moe_decode") or {}
    routed = moe.get("moe_assignments")
    return moe["moe_rows_computed"] / routed if routed else None
