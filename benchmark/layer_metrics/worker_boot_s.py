"""Seconds from ``Popen`` to ``worker_ready`` (``t_spawn`` to ``t_ready``) of
the process that holds the cell's chips: the interpreter, the imports, the
connection. Off the chip no row holds one; the longest-lived row stands in."""

from benchmark.lib import lifecycle_record


def read(run):
    rows = [r for r in lifecycle_record.rows() or ()
            if r["t_ready"] is not None]
    if not rows:
        return None
    row = max(rows, key=lambda r: (len(r["chips"]),
                                   (r["t_gone"] or r["t_ready"]) - r["t_spawn"]))
    return row["t_ready"] - row["t_spawn"]
