"""The flash-attention kernels by the name they give their call
(``ray_tpu/ops/pallas/flash.py``):
``flash_<kind>_bh<bh>_q<sq>_k<sk>_d<d>_c<causal>_w<window>``, the true
lengths before padding, ``w0`` for no band. ``benchmark/kernels/flash.py``
goes by the result's shape and costs every call as a full causal square;
this file costs a call by the (query, key) pairs its mask leaves alive:

- a causal call: a query at p sees min(p + 1, sk) keys; with a window w the
  keys in (p - w, p];
- operations: each of the kernel's products (fwd 2, dq 3, dkv 4) is
  2 * d a live pair and head. A pair a block computes and then masks is
  work the kernel does and the model does not need: not counted;
- bytes: every operand and result read or written once.

A checkout whose kernels carry the old names (``flash_fwd``) gives no event
this matches.
"""

import re
from typing import Optional, Tuple

# the instruction is named after the call and the transforms it was traced
# under: ``%jvp_flash_fwd_.._w4096_.3``, ``%transpose_jvp_flash_dq_.._w0__.1``
_CALL = re.compile(r"^%?\w*?flash_(fwd|dq|dkv)_bh(\d+)_q(\d+)_k(\d+)_d(\d+)"
                   r"_c([01])_w(\d+)[_.\d]* = (.*?)custom-call\(")
_RESULT = re.compile(r"\b(bf16|f16|f32)\[")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}
_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def live_pairs(sq: int, sk: int, causal: bool, window: int) -> int:
    """(query, key) pairs of one head that the mask leaves alive, the first
    query at position 0."""
    if not causal:
        return sq * sk
    return sum(min(p + 1, sk) - (max(0, p + 1 - window) if window else 0)
               for p in range(sq))


def call_shape(event_name: str
               ) -> Optional[Tuple[str, int, int, int, int, bool, int, int]]:
    """(kind, batch*heads, sq, sk, head_dim, causal, window, itemsize) of a
    named flash call's event, else None."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    kind, bh, sq, sk, d, causal, window, result = m.groups()
    dtype = _RESULT.search(result)
    return (kind, int(bh), int(sq), int(sk), int(d), causal == "1",
            int(window), _ITEM[dtype.group(1)] if dtype else 2)


def call_cost(kind: str, bh: int, sq: int, sk: int, d: int, causal: bool,
              window: int, itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) the call needs."""
    flops = _PRODUCTS[kind] * 2.0 * d * bh * live_pairs(sq, sk, causal, window)
    # q k v o | q k v o do dq | q k v o do dk dv, as kernels/flash.py
    rows = {"fwd": 2 * sq + 2 * sk, "dq": 4 * sq + 2 * sk,
            "dkv": 3 * sq + 4 * sk}[kind]
    return flops, float(rows * bh * d * itemsize)


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a named flash call."""
    call = call_shape(event_name)
    return call_cost(*call) if call else None
