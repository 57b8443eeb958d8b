"""The write of a decode step's new keys and values into buffers kept a head's
positions together (``ray_tpu/ops/pallas/kv_write.py``), from what a trace
event says of itself: a ``tpu_custom_call`` named
``kv_write_r<rows>_h<heads>_t<tile>_d<d>`` whose results are the buffers it
writes (the whole of each, aliased: the name carries what it moves of them).

- bytes: for every buffer and row one tile ``[heads, tile, d]`` read and
  written back, in the buffer's type;
- operations: one select an element of it."""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?kv_write_r(\d+)_h(\d+)_t(\d+)_d(\d+)[.\d]* = (.*?)custom-call\(")
_RESULT = re.compile(r"\b(bf16|f16|f32)\[")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is the write."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    rows, h, t, d = (int(v) for v in m.groups()[:4])
    elements = rows * h * t * d
    sizes = [_ITEMSIZE[kind] for kind in _RESULT.findall(m.group(5))] or [2, 2]
    return float(len(sizes) * elements), 2.0 * elements * sum(sizes)
