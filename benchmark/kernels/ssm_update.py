"""The one-token recurrent update of a decode step as a Pallas call
(``ray_tpu/ops/pallas/ssm_update.py``), from what a trace event says of
itself: a ``tpu_custom_call`` named ``ssm_update_r<rows>_h<heads>_p<p>_n<n>``
(the name carries the rows it steps, which its result, the whole stacked
state, does not show).

- operations: 5 an element of the rows' state (decay, the outer product's
  two, the add, the product with ``C``; the sum over ``n`` one more: 6);
- bytes: the rows' float32 state read once and written once.

Where the update is XLA fusions no event carries this name and ``match``
finds nothing; the reader then goes by the scope's seconds
(``layer_metrics/ssm_update_roofline.py``)."""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?ssm_update_r(\d+)_h(\d+)_p(\d+)_n(\d+)[.\d]* = ")


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is the update."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    rows, h, p, n = (int(v) for v in m.groups())
    elements = rows * h * p * n
    return 6.0 * elements, 2.0 * 4 * elements
