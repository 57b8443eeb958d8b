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
        q_offset: int = 0,
        window: Optional[int] = None,
        kv_heads_major: bool = False) -> jax.Array:
    """Attention over [batch, seq, heads, head_dim] tensors.

    Supports GQA: k/v may have fewer heads than q as long as
    ``q_heads % kv_heads == 0``. ``q_offset`` is the absolute position of
    q[0] relative to k (for decode with a KV cache): one value for the
    batch, or one per row ([batch]). Softmax in fp32. ``window``: a query
    at position ``i`` sees key ``j`` iff ``0 <= i - j < window`` (with
    ``causal``). ``kv_heads_major``: ``k`` and ``v`` come
    [batch, kv_heads, seq, head_dim], a head's positions together, as a
    cache keeps them whose positions would otherwise share a tile with too
    few heads to fill it.
    """
    b, sq, hq, d = q.shape
    if kv_heads_major:
        return _mha_heads_major(q, k, v, scale, q_offset, window)
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
        mask = qpos >= kpos
        if window is not None:
            mask = mask & (qpos - kpos < window)
        mask = mask.reshape(-1, 1, sq, sk)
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


def _mha_heads_major(q, k, v, scale, q_offset, window):
    """``mha``, causal and grouped, over ``k``, ``v`` [b, hkv, sk, d]."""
    b, sq, hq, d = q.shape
    _, hkv, sk, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    q = q.reshape(b, sq, hkv, hq // hkv, d)
    logits = jnp.einsum("bqhgd,bhkd->bhgqk", q * scale, k,
                        preferred_element_type=jnp.float32)
    qpos = jnp.arange(sq)[:, None] + jnp.asarray(q_offset)[..., None, None]
    kpos = jnp.arange(sk)[None, :]
    mask = qpos >= kpos
    if window is not None:
        mask = mask & (qpos - kpos < window)
    logits = jnp.where(mask.reshape(-1, 1, 1, sq, sk), logits, NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bhkd->bqhgd", weights, v)
    return out.reshape(b, sq, hq, d)


def pad_diff_queries(q: jax.Array) -> jax.Array:
    """Differential attention's queries as plain grouped-query ones over
    key/value heads taken in PAIRS. ``q`` [b, s, hq, hd] with heads in
    stripes: pair ``j`` is ``(q[2j], q[2j+1])`` and reads the key pair
    ``g = j // 2``, the first query against ``k[2g]`` and the second
    against ``k[2g+1]``. With a pair of key heads kept side by side as one
    head of ``2 hd`` (``[k[2g] | k[2g+1]]``, which is how they lie in a
    row of ``[hkv, hd]`` anyway), ``q[2j]`` padded with zeros on the right
    and ``q[2j+1]`` on the left give the same two scores. Returns
    [b, s, hq, 2 hd]: four query heads a key pair."""
    b, s, hq, hd = q.shape
    q = q.reshape(b, s, hq // 2, 2, hd)
    zeros = jnp.zeros_like(q[..., 0, :])
    first = jnp.concatenate([q[..., 0, :], zeros], axis=-1)
    second = jnp.concatenate([zeros, q[..., 1, :]], axis=-1)
    return jnp.stack([first, second], axis=-2).reshape(b, s, hq, 2 * hd)


def diff_combine(out: jax.Array, lam: jax.Array, lam_init: jax.Array,
                 subln: jax.Array, eps: float) -> jax.Array:
    """The differential combination of what ``mha`` gave for
    ``pad_diff_queries``'s heads against paired keys AND paired values:
    ``out`` [b, s, hq, 2 hd] holds for pair ``j`` ``A_1 vv`` at head
    ``2j`` and ``A_2 vv`` at ``2j + 1`` (``vv`` the value pair, ``2 hd``
    wide), and ``(A_1 - lam A_2) vv`` is their difference, since the
    softmaxes are normalised each by itself; taken in float32, of two
    sums each rounded to ``out``'s type as every ``mha`` result is. Then
    the sub-norm (an RMSNorm
    over ``2 hd`` with weight ``subln``) times ``1 - lam_init``, in
    float32. Returns [b, s, hq * hd] float32: heads ``2j``, ``2j + 1`` of
    the published layout are the result's halves."""
    b, s, hq, wide = out.shape
    out = out.astype(jnp.float32).reshape(b, s, hq // 2, 2, wide)
    diff = out[..., 0, :] - lam * out[..., 1, :]
    rms = jax.lax.rsqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + eps)
    diff = diff * rms * subln.astype(jnp.float32) * (1.0 - lam_init)
    return diff.reshape(b, s, hq // 2 * wide)


def diff_attention(q: jax.Array, k: jax.Array, v: jax.Array, lam, lam_init,
                   subln: jax.Array, *, eps: float, scale: float,
                   q_offset=0, window: Optional[int] = None) -> jax.Array:
    """Differential attention (Ye et al. 2024, as Phi-4-mini-flash pairs
    its heads): ``q`` [b, sq, hq, hd]; ``k``, ``v`` [b, sk, hkv / 2, 2 hd],
    key/value heads in pairs side by side; ``lam``, ``lam_init`` scalars;
    ``subln`` [2 hd]. Two softmaxes a pair, in float32, one value pair,
    the sub-norm: ``pad_diff_queries`` -> ``mha`` -> ``diff_combine``.
    Returns [b, sq, hq * hd] float32."""
    out = mha(pad_diff_queries(q), k, v, causal=True, q_offset=q_offset,
              scale=scale, window=window)
    return diff_combine(out, lam, lam_init, subln, eps)
