"""The one-token S6 (Mamba-1) update of a decode step as a Pallas call
(``ray_tpu/ops/pallas/s6_update.py``), from what a trace event says of itself:
a ``tpu_custom_call`` named ``s6_update_r<rows>_n<n>_c<c>`` (the name carries
the rows it steps, which its result, the whole stacked state, does not show).

- operations: 7 an element of the rows' state ``[n, c]`` (``dt A``, its
  exponential, the decay's product, the outer product's one, the add, the
  product with ``C`` and its sum over ``n``);
- bytes: the rows' float32 state read once and written once."""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?s6_update_r(\d+)_n(\d+)_c(\d+)[.\d]* = ")


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is the update."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    rows, n, c = (int(v) for v in m.groups())
    elements = rows * n * c
    return 7.0 * elements, 2.0 * 4 * elements
