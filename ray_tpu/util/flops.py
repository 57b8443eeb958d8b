"""Analytic FLOP accounting for the model zoo: a train step's work and MFU.

The training flight recorder's MFU waterfall (``util/train_recorder.py``,
fed by ``train/driver.py``) needs three ingredients: a parameter count, a
per-token FLOP estimate, and a peak-FLOPs denominator. This module is the
single home for those formulas so the numbers agree everywhere (Podracer,
arXiv:2104.06272, makes the same accounting the basis of TPU throughput
work).

Conventions (the standard scaling-book estimates):
  - A matmul touching N parameters costs 2N FLOPs per token forward and
    4N backward, so a train step is ~6N per token plus the attention
    quadratic term (causal halves it): 6*L*S*d per token.
  - MoE counts ACTIVE parameters (top-k experts), not total.
Embedding/head params are included: at the small-vocab presets they are
a real fraction of the work.
"""

from __future__ import annotations

from typing import Optional

# Peak dense bf16 FLOP/s of one chip, keyed by ``jax.Device.device_kind``.
# v5e: 197 TFLOP/s (Google Cloud documentation, "TPU v5e"). A device that
# is not in the table has no peak and so no MFU: that is an error, never a
# default, and a CPU run reports no MFU at all.
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def peak_flops_per_chip(device_kind: Optional[str] = None) -> float:
    """Peak FLOP/s of one device of ``device_kind`` (default: this
    process's first device); raises on a device the table does not know."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAK_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind {device_kind!r}; "
            f"add it to PEAK_FLOPS with its source") from None


def _flops_params(cfg) -> int:
    """The FLOPs-relevant parameter count: active params for MoE (top-k
    experts per token), total params otherwise."""
    active = getattr(cfg, "active_params", None)
    return active() if callable(active) else cfg.num_params()


def _attention_madds(cfg, seq: int) -> float:
    """Multiply-adds of the mixers' own products (not their projections)
    for one token of a ``seq``-token causal sequence, forward, summed over
    the layers, each by its kind (``models/moe.py``'s patterned configs name
    them; every other config's layers are ``full``): the keys a query sees
    (half the square; less the triangle below the band for ``window``)
    times the widths of the score and value products, which latent
    attention (``mla``) has apart; for ``kda`` the chunked form's, five
    products of chunk x width and three of width x width a head; for
    ``eva`` (a dense config's ``attn_kind``) the pairs a query sees, its
    window's causal half and one summary a chunk of every earlier window,
    and the pooling that makes a summary (a key and a value a position);
    for ``sparse`` the main attention over the pairs the indexer CHOSE
    (``min(t + 1, index_topk)`` a query, the model's work whatever a kernel
    walks), the indexer's scores over the causal pairs, and the second
    ``Q K^T`` over the chosen pairs that its loss's target takes, which
    runs forward alone and so counts a third (a multiply-add here is six
    operations a step). A prediction module's layer (``n_mtp_modules``) is one more of the last
    layer's kind."""
    kinds = getattr(cfg, "layer_kinds", ()) or (
        getattr(cfg, "attn_kind", "full"),) * cfg.n_layers
    kinds = kinds + kinds[-1:] * getattr(cfg, "n_mtp_modules", 0)
    w = min(getattr(cfg, "sliding_window", None) or seq, seq)

    def layer(kind: str) -> float:
        if kind == "eva":
            from ray_tpu.ops.eva import visible_pairs

            pairs = sum(visible_pairs(seq, cfg.eva_window, cfg.eva_chunk))
            return cfg.n_heads * cfg.head_dim * (2.0 * pairs / seq + 2)
        if kind == "kda":
            from ray_tpu.ops.kda import CHUNK

            return cfg.kda_heads * cfg.kda_head_dim * (
                5 * CHUNK + 3 * cfg.kda_head_dim)
        if kind == "mla":
            return cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                                  + cfg.v_head_dim) * seq / 2.0
        if kind == "sparse":
            from ray_tpu.ops.sparse_index import chosen_pairs

            chosen = chosen_pairs(seq, cfg.index_topk) / seq
            return (cfg.n_heads * cfg.head_dim * chosen * (2 + 1 / 3.0)
                    + cfg.index_heads * cfg.index_head_dim * (seq + 1) / 2.0)
        keys = w - w * w / (2.0 * seq) if kind == "window" else seq / 2.0
        return 2 * keys * cfg.n_heads * cfg.head_dim

    return sum(map(layer, kinds))


def train_flops_per_token(cfg, seq: int) -> float:
    """Fwd+bwd FLOPs per trained token: 6N + the mixers' own products; N
    holds a prediction module's matrices (its layer, its projection of two
    inputs) and a low-rank query's two factors, and the head counts once
    more for every module that projects through it too."""
    again = getattr(cfg, "n_mtp_modules", 0) * cfg.d_model * cfg.vocab_size
    return (6.0 * (_flops_params(cfg) + again)
            + 6 * _attention_madds(cfg, seq))


def mfu(flops: float, seconds: float, n_devices: int = 1,
        peak_per_chip: Optional[float] = None) -> float:
    """Model-FLOPs utilization: analytic work / (wall * aggregate peak)."""
    if seconds <= 0 or flops <= 0:
        return 0.0
    peak = peak_per_chip if peak_per_chip is not None \
        else peak_flops_per_chip()
    return flops / (seconds * peak * max(1, n_devices))
