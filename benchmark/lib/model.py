"""The device as the process that holds it sees it. Runs in the worker that
was granted the chip; the parent never imports JAX."""

from __future__ import annotations

import os
from typing import Any, Dict


def device_facts() -> Dict[str, Any]:
    """Platform, kind, count and the peak memory of the fullest chip."""
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    limit = (devices[0].memory_stats() or {}).get("bytes_limit", 0)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks)),
            "memory_limit_bytes": int(limit), "pid": os.getpid(),
            "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
