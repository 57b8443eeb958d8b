"""What the per-layer metrics of set-up and tear-down read: the program's
own record of the run (``ray_tpu/util/lifecycle.py``), asked in this process.
The parent of a cell has imported ``ray_tpu`` and hosted the raylet, so the
record is here once ``ray_tpu.shutdown()`` has returned; it is found through
``sys.modules`` because a reader imports neither the program nor JAX. Every
function gives None where the program keeps no such record (a tree from
before it) or the record holds no such entry.
"""

from __future__ import annotations

import sys
from typing import Any, Dict, List, Optional


def _module() -> Optional[Any]:
    return sys.modules.get("ray_tpu.util.lifecycle")


def span_s(name: str) -> Optional[float]:
    """Seconds of the session's newest span of that name."""
    lc = _module()
    found = lc.last(name) if lc is not None else None
    return None if found is None else found["t1"] - found["t0"]


def rows() -> Optional[List[Dict[str, Any]]]:
    """The session's rows, one per process the runtime spawned."""
    lc = _module()
    return lc.processes() if lc is not None else None


def shutdown() -> Optional[Dict[str, Any]]:
    """What ``ray_tpu.shutdown()`` recorded of the session just ended."""
    lc = _module()
    rec = lc.last_shutdown() if lc is not None else None
    return rec if rec is not None and rec["session"] == lc.session() else None
