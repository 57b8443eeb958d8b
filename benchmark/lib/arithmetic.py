"""The yardstick's arithmetic: peaks, parameter counts, operations and bytes.

Kept with the benchmark so that no PR that claims a gain can change what a
token costs. Everything is computed from the published configuration
(``config`` of a file under ``benchmark/configs``) and the depth the cell runs.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

# Published peaks of one chip, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
# A device that is not here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def head_dim(hf: Dict[str, Any]) -> int:
    return int(hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"])


def layer_matmul_params(hf: Dict[str, Any], active_only: bool = True) -> int:
    """Parameters of one layer's matrix multiplications (norms left out).
    With sparse experts: the experts a token is routed to (``active_only``)
    or all of them, plus the router."""
    d, f = hf["hidden_size"], hf["intermediate_size"]
    q = hf["num_attention_heads"] * head_dim(hf)
    kv = hf["num_key_value_heads"] * head_dim(hf)
    attn = d * q + 2 * d * kv + q * d
    experts = hf.get("num_local_experts")
    if not experts:
        return attn + 3 * d * f
    used = hf["num_experts_per_tok"] if active_only else experts
    return attn + used * 3 * d * f + d * experts


def total_params(hf: Dict[str, Any], n_layers: int) -> int:
    d, v = hf["hidden_size"], hf["vocab_size"]
    head = 0 if hf.get("tie_word_embeddings") else d * v
    return (v * d + head + d
            + n_layers * (layer_matmul_params(hf, active_only=False) + 2 * d))


def train_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Operations the forward and backward passes need for one token of a
    ``seq``-token causal sequence: 6 per parameter of every matrix product
    outside the embedding lookup (the head is one; with experts, the routed
    ones only, capacity padding not counted) and causal attention at half
    the square. Recomputation under remat does not count."""
    matmul = n_layers * layer_matmul_params(hf) + hf["hidden_size"] * hf["vocab_size"]
    attn = n_layers * hf["num_attention_heads"] * head_dim(hf) * seq
    return 6.0 * matmul + 6.0 * attn


def weight_bytes(hf: Dict[str, Any], n_layers: int, itemsize: int = 2) -> int:
    """What one decode step has to read of the weights: every layer and the
    head (the embedding gives up a row per token, which is nothing)."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    return itemsize * (n_layers * (layer_matmul_params(hf, active_only=False) + 2 * d)
                       + d * v + d)


def kv_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                          itemsize: int = 2) -> int:
    return 2 * n_layers * hf["num_key_value_heads"] * head_dim(hf) * itemsize


# ---- the flash kernel, from what a trace event says of itself ---------------

_SHAPE = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
# matrix products of [s, d] x [d, s] size in each kernel (ops/pallas/flash.py:
# _fwd_kernel 2, _dq_kernel 3, _dkv_kernel 4), told apart by what they return
_FLASH_PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}
_ITEM = {"bf16": 2, "f16": 2, "f32": 4}


def flash_call_kind(event_name: str) -> Optional[Tuple[str, int, int, int, int]]:
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


def flash_call_cost(kind: str, bh: int, s: int, d: int, itemsize: int
                    ) -> Tuple[float, float]:
    """(operations, bytes) one causal call needs: each product is
    2 * s * s * d per head at half the square; bytes are every operand and
    result read or written once."""
    flops = _FLASH_PRODUCTS[kind] * bh * s * s * d  # 2 * (s*s/2) * d each
    arrays = {"fwd": 4, "dq": 6, "dkv": 7}[kind]  # q k v o | q k v o do dq | .. dk dv
    return float(flops), float(arrays * bh * s * d * itemsize)
