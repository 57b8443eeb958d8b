"""The flash-attention kernels (``ray_tpu/ops/pallas/flash.py``), from what
a trace event says of itself: which of the three a ``tpu_custom_call`` is,
and the operations and bytes that call needs."""

import re
from typing import Optional, Tuple

_SHAPE = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
# matrix products of [s, d] x [d, s] size in each kernel (ops/pallas/flash.py:
# _fwd_kernel 2, _dq_kernel 3, _dkv_kernel 4), told apart by what they return
_FLASH_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


def call_kind(event_name: str) -> Optional[Tuple[str, int, int, int, int]]:
    """(kind, batch*heads, seq, head_dim, itemsize) of a ``tpu_custom_call``
    event whose result looks like one of the flash kernels', else None. The
    kernels carry no name of their own in a trace (``pallas_call`` is given
    none), so the result's shape has to do: (o, lse) is the forward, one
    array is dq, a pair of equal arrays is (dk, dv)."""
    if "tpu_custom_call" not in event_name or " custom-call(" not in event_name:
        return None
    result = event_name.split(" custom-call(", 1)[0].split(" = ", 1)[-1]
    shapes = _SHAPE.findall(result)
    if not shapes:
        return None
    dtype, bh, s, d = shapes[0]
    if len(shapes) == 1:
        kind = "dq"
    elif len(shapes) == 2 and shapes[1][3] == "1":
        kind = "fwd"
    elif len(shapes) == 2 and shapes[1][1:] == shapes[0][1:]:
        kind = "dkv"
    else:
        return None
    return kind, int(bh), int(s), int(d), _ITEM[dtype]


def call_cost(kind: str, bh: int, s: int, d: int, itemsize: int
              ) -> Tuple[float, float]:
    """(operations, bytes) one causal call needs: each product is
    2 * s * s * d per head at half the square; bytes are every operand and
    result read or written once."""
    flops = _FLASH_PRODUCTS[kind] * bh * s * s * d  # 2 * (s*s/2) * d each
    arrays = {"fwd": 4, "dq": 6, "dkv": 7}[kind]  # q k v o | q k v o do dq | .. dk dv
    return float(flops), float(arrays * bh * s * d * itemsize)


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a flash call."""
    call = call_kind(event_name)
    return call_cost(*call) if call else None
