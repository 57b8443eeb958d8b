"""Of the (token, expert) assignments the step's routers made, the share
that landed on an expert held on this chip (the train recorder's counters
``moe_held`` over ``moe_assignments``, over the measured window's launches,
warm-up left out, as the trainer's process kept them:
``benchmark/lib/launch_record.py``). With 8 of
256 experts held and a router that favours none, 3.1: how near the chip's
experts are to their deployed load, where they would see 32 chips' tokens.

The counters are the patterned training path's, which only a program that
can build the trinity_afmoe family's config has: this file asks the family whether
the checkout's does, as the cell is loaded, so that a checkout that cannot
train the cell fails before it starts a trainer (``trinity_afmoe.require_program``)."""

from benchmark.lib import launch_record, spec

spec.load_family("trinity_afmoe", spec.root_of(__file__)).require_program()


def read(run):
    r = launch_record.window_sums(run)
    return (100.0 * r.get("moe_held", 0) / r["moe_assignments"]
            if r and r.get("moe_assignments") else None)
