"""The grouped matrix products of the served MoE block
(``ray_tpu/ops/pallas/grouped_matmul.py``), from what a trace event says of
itself: a ``tpu_custom_call`` named ``moe_gmm[_swiglu]_e<experts>_k<k>_t<tile>``
whose result is [rows, n]: rows in tiles of ``tile``, each tile times its
expert's [k, n] matrix (with ``_swiglu`` two matrices: the SwiGLU's first
half).

What such a call needs is counted from these static shapes alone, so both
counts are held to what any routing needs:

- operations: 2 * k * n for each routed row and matrix. The rows are the
  routed assignments in groups padded to whole tiles, at most
  ``experts * (tile - 1)`` of padding: rows less that is a floor of the
  routed rows, and is what is counted;
- bytes: every expert's [k, n] matrices once (a tile reads its expert's),
  the routed rows in and their results out. An expert that no row was routed
  to is not read: the reader (``layer_metrics/moe_gmm_roofline.py``) scales
  the bytes by the share of experts the program's counters say were touched.
"""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?moe_gmm(_swiglu)?_e(\d+)_k(\d+)_t(\d+)[.\d]* = "
                   r"(bf16|f16|f32)\[(\d+),(\d+)\]")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


def call_shape(event_name: str) -> Optional[Tuple[int, int, int, int, int, int, int]]:
    """(matrices, rows, tile, experts, k, n, itemsize) of a grouped
    product's event, else None."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    swiglu, experts, k, tile, dtype, rows, n = m.groups()
    return (2 if swiglu else 1, int(rows), int(tile), int(experts), int(k),
            int(n), _ITEM[dtype])


def call_cost(matrices: int, rows: int, tile: int, experts: int, k: int, n: int,
              itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) one call needs at the least (header)."""
    routed = max(tile, rows - experts * (tile - 1))
    flops = 2.0 * matrices * routed * k * n
    nbytes = itemsize * (matrices * experts * k * n + routed * (k + n))
    return flops, float(nbytes)


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a grouped product."""
    call = call_shape(event_name)
    return call_cost(*call) if call else None
