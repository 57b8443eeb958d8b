"""The chunked delta rule a training step runs in each KDA layer
(``ray_tpu/ops/kda.py:kda_chunked``, forward and backward): what one layer's
recurrence over ``s`` tokens needs, from the published sizes. It is XLA
fusions and two loops over the chunks under the scope ``kda_scan`` inside
``attn_kda``, not one named call, so ``match`` finds no event unless a later
program names a Pallas call
``kda_<fwd|bwd..>_bh<..>_s<..>_c<chunk>_k<dk>_v<dv>``, whose cost is then its
share of ``scan_cost`` by its direction. No reader takes ``scan_cost`` yet:
the share of the roofline by scope (``scan_cost`` x KDA layers x the traced
stretch's steps over the time under ``attn_kda``'s ``kda_scan``) needs a
reduction that keeps an inner scope, which ``lib/trace.py:scope_of`` does
not (``PERF.md`` section 7).

- operations, with C the chunk (64), w the head width, H heads and whole
  chunks (the padding to whole chunks is computed, and counted: it is what
  the chunked form costs): forward, five products of C x w a token and head
  (``K K^T``, ``Q K^T``, ``T K``, ``T V``, ``A U~``) and three of w x w
  (``W S``, ``Q S``, ``K^T U~``), 2 operations a multiply-add; the backward
  twice that, as every product's is. The sums over pairs inside a
  sub-block, the triangular inverse and the elementwise decays are work the
  form does beside its products and are left out, as is the forward that a
  rematted backward runs again: the share reads low by them, never high;
- bytes: forward ``q``, ``k``, ``v`` in bf16, ``g`` in float32 and ``beta``
  in float32 read, ``o`` written in bf16; backward those read again with
  ``do``, and the five cotangents written. What an implementation keeps
  between the two (states at chunks' or segments' starts) is its own and not
  counted."""

import re
from typing import Any, Dict, Optional, Tuple

CHUNK = 64

_CALL = re.compile(r"^%?\w*?kda_(fwd|bwd)\w*?_bh(\d+)_s(\d+)_c(\d+)_k(\d+)_v(\d+)"
                   r"[_.\d]* = .*?custom-call\(")


def sizes(hf: Dict[str, Any]) -> Tuple[int, int]:
    kda = hf["linear_attn_config"]
    return kda["num_heads"], kda["head_dim"]


def scan_cost(s: int, hf: Dict[str, Any]) -> Tuple[float, float]:
    """(operations, bytes) of one KDA layer's recurrence over ``s`` tokens,
    forward and backward."""
    h, w = sizes(hf)
    tokens = -(-s // CHUNK) * CHUNK
    forward = 2.0 * tokens * h * w * (5 * CHUNK + 3 * w)
    rows = s * h * (w * (2 + 2 + 2 + 4) + 4.0)      # q k v g beta
    out = s * h * w * 2.0
    return 3.0 * forward, (rows + out) + (rows + 2 * out + rows)


def match(event_name: str) -> Optional[Tuple[float, float]]:
    """(operations, bytes) of one device event if it is a named KDA call: a
    third of ``scan_cost`` forward, two thirds backward."""
    m = _CALL.match(event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    way, bh, s, _, k, _ = m.groups()
    flops, nbytes = scan_cost(int(s), {"linear_attn_config": {
        "num_heads": int(bh), "head_dim": int(k)}})
    share = 1 / 3 if way == "fwd" else 2 / 3
    return flops * share, nbytes * share
