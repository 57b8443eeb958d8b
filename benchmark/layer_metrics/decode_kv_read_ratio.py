"""Positions the decode steps' attention read (rows x the step's bound,
``generate.kv_read_bound``) over positions their active rows had live, over
the window's decode launches (the engine recorder's ``kv_positions_read``
and ``kv_positions_live``, reckoned on the host from the positions each
launch was staged with): 1.0 would be a read of the live positions alone; a
read of every allocated position is ``max_len`` over the rows' mean length
(5.5 in ``decode-full``). An engine that records no such counter (a program
whose read has no bound) gives nothing."""


def read(run):
    engine = run.get("engine", {})
    live = engine.get("kv_positions_live")
    return engine["kv_positions_read"] / live if live else None
