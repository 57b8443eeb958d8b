"""The chunked (SSD) scan a prefill runs over a prompt in each mamba layer
(``ray_tpu/ops/ssm.py:ssd_scan``): what one layer's scan of ``s`` tokens
needs, from the published sizes. It is XLA fusions under the scope
``ssm_scan``, not one named call, so ``match`` finds no event; the reader
(``layer_metrics/ssd_prefill_roofline.py``) takes ``scan_cost``.

- operations, with ``q = min(chunk, s)`` and ``c`` whole chunks (the padding
  to whole chunks is computed, and counted: it is what the chunked form
  costs): ``C B^T`` 2 c q^2 g n; the masked product with ``dt x`` 2 c q^2 h p;
  each chunk's summed state and the read of the entering state, 2 c q h p n
  each. The decay mask and the cumulative sums are elementwise and left out;
- bytes: ``x``, ``B``, ``C`` in and ``y`` out in bf16, ``dt`` in float32, and
  the final state out in float32."""

from typing import Any, Dict, Optional, Tuple


def scan_cost(s: int, hf: Dict[str, Any]) -> Tuple[float, float]:
    """(operations, bytes) of one mamba layer's scan over ``s`` tokens."""
    h, p, n = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    g = hf["mamba_n_groups"]
    q = min(hf["mamba_chunk_size"], s)
    c = -(-s // q)
    flops = 2.0 * c * q * (q * g * n + q * h * p + 2 * h * p * n)
    nbytes = 2.0 * s * (2 * h * p + 2 * g * n) + 4.0 * s * h + 4.0 * h * p * n
    return flops, nbytes


def match(event_name: str) -> Optional[Tuple[float, float]]:
    return None
