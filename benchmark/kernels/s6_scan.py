"""The S6 (Mamba-1) scan a prefill runs over a prompt in each mamba layer
(``ray_tpu/ops/ssm.py:s6_scan``): what one layer's scan of ``s`` tokens needs,
from the sizes the family's arithmetic gives (``d_inner`` = 2 x hidden,
``d_state`` 16). It is XLA fusions under the scope ``ssm_scan``, a loop over
time, not one named call, so ``match`` finds no event; the reader
(``layer_metrics/s6_scan_roofline.py``) takes ``scan_cost``.

- operations: 7 an element of the state ``[d_state, d_inner]`` a token, as the
  one-token update (``s6_update.py``), and the product ``dt x``;
- bytes: ``x``, ``B``, ``C`` in and ``y`` out in bf16, ``dt`` in float32, and
  the state in and out once in float32 (it stays on the chip between tokens)."""

from typing import Any, Dict, Optional, Tuple

D_STATE, EXPAND = 16, 2


def scan_cost(s: int, hf: Dict[str, Any]) -> Tuple[float, float]:
    """(operations, bytes) of one mamba layer's scan over ``s`` tokens."""
    c, n = EXPAND * hf["hidden_size"], D_STATE
    flops = s * (7.0 * n * c + c)
    nbytes = 2.0 * s * (2 * c + 2 * n) + 4.0 * s * c + 2 * 4.0 * n * c
    return flops, nbytes


def match(event_name: str) -> Optional[Tuple[float, float]]:
    return None
