"""The yardstick's arithmetic: peaks, parameter counts, operations and bytes.

Kept with the benchmark so that no PR that claims a gain can change what a
token costs. Everything is computed from the published configuration
(``config`` of a file under ``benchmark/configs``), the depth the cell runs
and the arithmetic of the configuration's family (``benchmark/families/``);
what a kernel's call costs is in ``benchmark/kernels/``.
"""

from __future__ import annotations

from typing import Any, Dict

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


# ---- sums over a family's arithmetic -------------------------------------------
# ``family`` is a module of ``benchmark/families/``: ``matmul_params(hf,
# n_layers, active_only)``, ``attention_flops_per_token(hf, n_layers, seq)``
# and ``cache_bytes_per_position(hf, n_layers, itemsize)`` over the whole
# depth, so that unlike layers can be counted. The embedding, the head and
# the norms (two a layer and the final one) are counted here.

def total_params(family: Any, hf: Dict[str, Any], n_layers: int) -> int:
    d, v = hf["hidden_size"], hf["vocab_size"]
    head = 0 if hf.get("tie_word_embeddings") else d * v
    return (v * d + head + d
            + family.matmul_params(hf, n_layers, active_only=False)
            + n_layers * 2 * d)


def train_flops_per_token(family: Any, hf: Dict[str, Any], n_layers: int,
                          seq: int) -> float:
    """Operations the forward and backward passes need for one token of a
    ``seq``-token causal sequence: 6 per parameter of every matrix product
    outside the embedding lookup (the head is one; with experts, the routed
    ones only, capacity padding not counted) and 6 per multiply-add of
    causal attention. Recomputation under remat does not count."""
    matmul = (family.matmul_params(hf, n_layers, active_only=True)
              + hf["hidden_size"] * hf["vocab_size"])
    return 6.0 * matmul + 6.0 * family.attention_flops_per_token(hf, n_layers, seq)


def weight_bytes(family: Any, hf: Dict[str, Any], n_layers: int,
                 itemsize: int = 2) -> int:
    """What one decode step has to read of the weights: every layer and the
    head (the embedding gives up a row per token, which is nothing)."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    return itemsize * (family.matmul_params(hf, n_layers, active_only=False)
                       + n_layers * 2 * d + d * v + d)
