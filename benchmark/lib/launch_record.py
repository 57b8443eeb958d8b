"""What the train recorder counted over a run's launches, as the trainer's
process kept it: the ``train_launches`` span of the program's own record
(``ray_tpu/util/lifecycle.py``), with the launches' number and extent and a
sparse model's routing counters, folded and launch by launch. Found through
``sys.modules`` like ``lifecycle_record.py``: a reader imports neither the
program nor JAX. None where the program keeps no such record (a tree from
before it)."""

from __future__ import annotations

import sys
from typing import Any, Dict, Optional


def totals() -> Optional[Dict[str, Any]]:
    lc = sys.modules.get("ray_tpu.util.lifecycle")
    return lc.last("train_launches") if lc is not None else None


def window_sums(run: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """The counters added up over the launches of the measured window: the
    run's launches in order less the mix's ``warmup_launches`` at the front
    and the probe (the first batch again) at the end. What is left is the
    window and the launch or two that were in flight when it closed. None
    where there is no record, {} where its launches counted nothing."""
    r = totals()
    if not r or "per_launch" not in r:
        return None
    rows = r["per_launch"][int(run["cell"]["traffic"]["warmup_launches"]):-1]
    out: Dict[str, int] = {}
    for row in rows:
        for name, v in row.items():
            out[name] = out.get(name, 0) + v
    return out
