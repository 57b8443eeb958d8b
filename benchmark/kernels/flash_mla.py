"""The flash-attention kernels at two head widths, by the name they give
their call (``ray_tpu/ops/pallas/flash.py``):
``flash_<kind>_bh<bh>_q<sq>_k<sk>_d<d>v<dv>_c<causal>_w<window>``, queries and
keys ``d`` wide and values ``dv`` (latent attention's uncompressed form: 192
and 128). A call of one width carries no ``v<dv>`` and is
``kernels/flash_band.py``'s; ``kernels/flash.py`` goes by the result's shape
and counts one width, so it is not asked about this cell.

- operations, a live (query, key) pair and head (``flash_band.live_pairs``:
  the mask's, the band's): the products that contract or produce ``d``
  columns (``Q K^T`` in all three kernels, ``dS K`` in dq, ``dS^T Q`` in
  dkv) are 2 d each, those over ``dv`` (``P V`` forward, ``dO V^T`` in dq
  and dkv, ``P^T dO`` in dkv) 2 dv each: fwd d + dv, dq 2 d + dv, dkv
  2 d + 2 dv multiply-adds. A pair a block computes and then masks is not
  counted, nor the half-empty second pass the MXU makes over a 192-wide
  contraction;
- bytes: every operand and result read or written once."""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?\w*?flash_(fwd|dq|dkv)_bh(\d+)_q(\d+)_k(\d+)_d(\d+)v(\d+)"
                   r"_c([01])_w(\d+)[_.\d]* = (.*?)custom-call\(")
_RESULT = re.compile(r"\b(bf16|f16|f32)\[")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}
# products a live pair pays, as (over d, over dv)
_PRODUCTS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}


def live_pairs(sq: int, sk: int, causal: bool, window: int) -> int:
    """As ``flash_band.live_pairs``: (query, key) pairs of one head that the
    mask leaves alive, the first query at position 0."""
    if not causal:
        return sq * sk
    return sum(min(p + 1, sk) - (max(0, p + 1 - window) if window else 0)
               for p in range(sq))


def call_shape(event_name: str):
    """(kind, batch*heads, sq, sk, d, dv, causal, window, itemsize) of a
    named two-width flash call's event, else None."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    kind, bh, sq, sk, d, dv, causal, window, result = m.groups()
    dtype = _RESULT.search(result)
    return (kind, int(bh), int(sq), int(sk), int(d), int(dv), causal == "1",
            int(window), _ITEM[dtype.group(1)] if dtype else 2)


def call_cost(kind: str, bh: int, sq: int, sk: int, d: int, dv: int,
              causal: bool, window: int, itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) the call needs."""
    over_d, over_dv = _PRODUCTS[kind]
    flops = 2.0 * (over_d * d + over_dv * dv) * bh * live_pairs(
        sq, sk, causal, window)
    # q k | v o, then do dq, then dk dv: columns read or written a row
    cols = {"fwd": (sq + sk) * d + (sk + sq) * dv,
            "dq": (2 * sq + sk) * d + (sk + 2 * sq) * dv,
            "dkv": (sq + 2 * sk) * d + (2 * sk + 2 * sq) * dv}[kind]
    return flops, float(cols * bh * itemsize)


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a named flash call
    at two widths."""
    call = call_shape(event_name)
    return call_cost(*call) if call else None
