"""Multi-head attention with GQA, causal masking, and segment ids.

XLA-path implementation: one fused softmax(QK^T)V chain that the TPU backend
tiles onto the MXU. A pallas flash-attention kernel (``ops/pallas/flash.py``)
overrides this on real TPUs for long sequences; this einsum form is the
always-correct fallback and the numerics reference for the kernel tests.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -2.0**30  # large finite negative; avoids NaN from (-inf) - (-inf)


def mha(q: jax.Array, k: jax.Array, v: jax.Array, *,
        causal: bool = True,
        segment_ids: Optional[jax.Array] = None,
        bias: Optional[jax.Array] = None,
        scale: Optional[float] = None,
        q_offset: int = 0) -> jax.Array:
    """Attention over [batch, seq, heads, head_dim] tensors.

    Supports GQA: k/v may have fewer heads than q as long as
    ``q_heads % kv_heads == 0``. ``q_offset`` is the absolute position of
    q[0] relative to k (for decode with a KV cache): one value for the
    batch, or one per row ([batch]). Softmax in fp32.
    """
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    if hq != hkv:
        if hq % hkv:
            raise ValueError(f"q heads {hq} not divisible by kv heads {hkv}")
        group = hq // hkv
        q = q.reshape(b, sq, hkv, group, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q * scale, k,
                            preferred_element_type=jnp.float32)
        logits = logits.reshape(b, hkv * group, sq, sk)
    else:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k,
                            preferred_element_type=jnp.float32)

    mask = None
    if causal:
        # [sq, 1], or [batch, sq, 1] under per-row offsets
        qpos = jnp.arange(sq)[:, None] + jnp.asarray(q_offset)[..., None, None]
        kpos = jnp.arange(sk)[None, :]
        mask = (qpos >= kpos).reshape(-1, 1, sq, sk)
    if segment_ids is not None:
        # [b, 1, sq, sk]; cross-segment attention is masked (packed sequences).
        seg_mask = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        mask = seg_mask if mask is None else (mask & seg_mask)
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    if bias is not None:
        logits = logits + bias

    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if hq != hkv:
        weights = weights.reshape(b, hkv, group, sq, sk)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)
        return out.reshape(b, sq, hq, d)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    return out
