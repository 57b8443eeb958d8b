"""The flash-attention kernels under a learned choice, by the name they give
their call (``ray_tpu/ops/pallas/flash.py``):
``flash_<kind>_bh<bh>_q<sq>_k<sk>_d<d>_c<causal>_w0_t<topk>``, a call whose
mask is data: an indexer's pick of at most ``topk`` keys a query, one for all
heads, read tile by tile beside K and V. A call without ``_t<topk>`` is
``kernels/flash_band.py``'s (whose pattern does not take this name) or
``kernels/flash_mla.py``'s.

- operations: the CHOSEN (query, key) pairs, ``min(p + 1, topk)`` for the
  query at p (``chosen_pairs``; ties at the threshold aside), times
  ``flash_mla.py``'s products a pair at one width (fwd d + d, dq 2 d + d,
  dkv 2 d + 2 d multiply-adds). The kernels walk every causal tile and mask:
  a pair a tile computes and the choice drops is work the kernel does and
  the model does not need, and is not counted, so at s 16,384 and topk
  2,048 a masked dense walk at the MXU's peak reads 23%: the number says
  what skipping or gathering would be worth, and reads the same work
  whatever implements the choice;
- bytes: every operand and result read or written once, the choice
  ([b, sq, sk] int8, once a batch row: bh / heads is not in the name, so it
  is counted once a call, its least) among them.
"""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?\w*?flash_(fwd|dq|dkv)_bh(\d+)_q(\d+)_k(\d+)_d(\d+)"
                   r"_c([01])_w0_t(\d+)[_.\d]* = (.*?)custom-call\(")
_RESULT = re.compile(r"\b(bf16|f16|f32)\[")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}
# products a chosen pair pays, each 2 d operations (flash_mla's at d = dv)
_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def chosen_pairs(sq: int, sk: int, causal: bool, topk: int) -> int:
    """(query, key) pairs of one head that a choice of ``topk`` keys a
    query keeps, ties aside, the first query at position 0."""
    if not causal:
        return sq * min(sk, topk)
    return sum(min(p + 1, sk, topk) for p in range(sq))


def call_shape(event_name: str):
    """(kind, batch*heads, sq, sk, d, causal, topk, itemsize) of a named
    flash call under a choice, else None."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    kind, bh, sq, sk, d, causal, topk, result = m.groups()
    dtype = _RESULT.search(result)
    return (kind, int(bh), int(sq), int(sk), int(d), causal == "1",
            int(topk), _ITEM[dtype.group(1)] if dtype else 2)


def call_cost(kind: str, bh: int, sq: int, sk: int, d: int, causal: bool,
              topk: int, itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) the call needs."""
    flops = _PRODUCTS[kind] * 2.0 * d * bh * chosen_pairs(sq, sk, causal, topk)
    # q k v o | q k v o do dq | q k v o do dk dv, as kernels/flash_band.py
    rows = {"fwd": 2 * sq + 2 * sk, "dq": 4 * sq + 2 * sk,
            "dkv": 3 * sq + 4 * sk}[kind]
    return flops, float(rows * bh * d * itemsize + sq * sk)


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a named flash call
    under a choice."""
    call = call_shape(event_name)
    return call_cost(*call) if call else None
