"""EVA attention's kernels by the name they give their call
(``ray_tpu/ops/pallas/eva_attn.py``):
``eva_attn_<fwd|dq|dkv|dsum>_bh<b*h>_s<seq>_d<d>_w<window>_c<chunk>``, the
sequence in whole windows. One softmax over two parts: a query sees the keys
of its own window at or before it (the LOCAL part: a causal half of every
``window`` x ``window`` block down the diagonal) and one summary a ``chunk``
of every earlier window (the SUMMARY part: whole blocks of ``window`` queries
x ``window / chunk`` summaries strictly below the diagonal). ``fwd`` and
``dq`` walk both parts, ``dkv`` the local part (the keys' and values'
gradients), ``dsum`` the summary part (the summaries' gradients).

- operations: each of a kernel's products (fwd 2, dq 3, dkv and dsum 4) is
  2 * d a VISIBLE pair and head. A pair a tile computes and then masks (the
  upper half of a diagonal tile) is work the kernel does and the model does
  not need: not counted;
- bytes: every operand and result the call needs read or written once: q,
  local keys and values, the summaries some query sees, ``o`` or ``do``,
  the float32 ``lse`` and ``delta`` rows, the gradients written.

``kernels/flash.py`` goes by a result's shape and would take these calls for
full causal squares; no metric of the cell reads it.
"""

import re
from typing import Optional, Tuple

_CALL = re.compile(r"^%?\w*?eva_attn_(fwd|dq|dkv|dsum)_bh(\d+)_s(\d+)_d(\d+)"
                   r"_w(\d+)_c(\d+)[_.\d]* = (.*?)custom-call\(")
_RESULT = re.compile(r"\b(bf16|f16|f32)\[")
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


def visible_pairs(seq: int, window: int, chunk: int) -> Tuple[int, int]:
    """(local, summary) visible (query, key) pairs of one head."""
    whole, rest = divmod(seq, window)
    local = whole * window * (window + 1) // 2 + rest * (rest + 1) // 2
    summary = (window // chunk) * (window * whole * (whole - 1) // 2
                                   + rest * whole)
    return local, summary


def call_shape(event_name: str
               ) -> Optional[Tuple[str, int, int, int, int, int, int]]:
    """(kind, batch*heads, seq, head_dim, window, chunk, itemsize) of a named
    EVA call's event, else None."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    kind, bh, s, d, w, c, result = m.groups()
    dtype = _RESULT.search(result)
    return (kind, int(bh), int(s), int(d), int(w), int(c),
            _ITEM[dtype.group(1)] if dtype else 2)


def call_cost(kind: str, bh: int, s: int, d: int, window: int, chunk: int,
              itemsize: int) -> Tuple[float, float]:
    """(operations, bytes) the call needs."""
    local, summary = visible_pairs(s, window, chunk)
    # summaries some query sees: all but the last window's
    seen = (window // chunk) * max(-(-s // window) - 1, 0)
    products, pairs, rows = {
        # q k v o | ks vs
        "fwd": (2, local + summary, 4 * s + 2 * seen),
        # q k v do dq | ks vs
        "dq": (3, local + summary, 5 * s + 2 * seen),
        # q k v do dk dv
        "dkv": (4, local, 6 * s),
        # q do | ks vs dks dvs
        "dsum": (4, summary, 2 * s + 4 * seen)}[kind]
    stats = s * 4 * (1 if kind == "fwd" else 2)          # lse, delta
    return (products * 2.0 * d * bh * pairs,
            float(bh * (rows * d * itemsize + stats)))


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a named EVA call."""
    call = call_shape(event_name)
    return call_cost(*call) if call else None
