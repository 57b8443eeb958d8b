"""A scope's share of the device's busy time in a traced run, for the
readers that report one scope of one program (``reduced["by_scope"]`` of
``benchmark/lib/trace.py``: an operation's own time under the outermost
``jax.named_scope`` of its ``op_name``)."""

from __future__ import annotations

from typing import Any, Dict, Optional


def share(run: Dict[str, Any], scope: str, program: str = "jit_steps"
          ) -> Optional[float]:
    """Percent of ``busy_s`` under ``<program>/<scope>``; None without a
    trace or where the program names no such scope (a tree from before it,
    another model's step)."""
    t = run.get("trace")
    own = t and t.get("by_scope", {}).get(f"{program}/{scope}")
    return 100.0 * own / t["busy_s"] if own and t.get("busy_s") else None
