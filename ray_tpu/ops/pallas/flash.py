"""Flash attention as pallas TPU kernels (fwd + bwd), with LSE output.

The memory-bound softmax(QK^T)V chain rewritten as the streaming-softmax
algorithm: the [seq, seq] score matrix never materializes in HBM; each grid
step keeps a [block_q, head_dim] accumulator plus running (max, sum) rows in
VMEM. The backward pass is two kernels (dq; dkv) over recomputed score
blocks, using the saved log-sum-exp instead of the softmax weights.

This replaces what the reference delegates to torch/CUDA libraries (it has no
attention kernels of its own — SURVEY.md §5 "Long-context: absent"); here it
is a first-class op because ring/context parallelism composes from the
``(out, lse)`` form (``ray_tpu/parallel/context.py``).

Layout: wrappers take [batch, seq, heads, head_dim] (framework convention),
kernels run on [batch*heads, seq, head_dim]. ``q_position_offset`` is a
dynamic scalar (SMEM) so ring attention can slide the causal mask per step.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF


def _needs_interpret() -> bool:
    """Interpret mode is for the CPU tests and nothing else: every other
    backend compiles the kernel, or fails where it cannot."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------- forward

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                 # outputs
                m_scr, l_scr, acc_scr,          # scratch
                *, scale, causal, block_q, block_k, kv_len):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # [bq, d]
    k = k_ref[0]                                   # [bk, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    qi = pl.program_id(1)
    kpos = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len                           # key padding
    if causal:
        qpos = (qi * block_q + qoff_ref[0]
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        mask = mask & (qpos >= kpos)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # [bq, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                         # [bq, bk] fp32
    # Fully-masked rows: m_new stays NEG_INF; exp(NEG_INF - NEG_INF)=1 would
    # poison p, so zero those rows explicitly.
    row_dead = m_new <= NEG_INF / 2
    p = jnp.where(row_dead, 0.0, p)
    alpha = jnp.where(row_dead, 0.0, alpha)

    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[...] + jnp.log(l_safe))
        lse_ref[0] = lse.astype(lse_ref.dtype)


def _flash_fwd_bhsd(q, k, v, q_offset, *, scale, causal, kv_len,
                    block_q, block_k, interpret) -> Tuple[jax.Array, jax.Array]:
    """q,k,v: [bh, s, d] (pre-padded to block multiples); returns (o, lse).

    ``kv_len`` is the TRUE (unpadded) key length — padded keys are masked.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=kv_len)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q_offset, q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward

def _dq_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr,
               *, scale, causal, block_q, block_k, kv_len):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kpos = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len
    if causal:
        qpos = (pl.program_id(1) * block_q + qoff_ref[0]
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        mask = mask & (qpos >= kpos)
    lse = lse_ref[0]                               # [bq, 1]
    p = jnp.where(mask & (lse > NEG_INF / 2), jnp.exp(s - lse), 0.0)
    do = do_ref[0].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0]) * scale
    dq_scr[...] += jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, block_q, block_k, kv_len):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    kk = pl.program_id(1)
    kpos = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_len
    if causal:
        qpos = (qi * block_q + qoff_ref[0]
                + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        mask = mask & (qpos >= kpos)
    lse = lse_ref[0]                               # [bq, 1]
    p = jnp.where(mask & (lse > NEG_INF / 2), jnp.exp(s - lse), 0.0)
    do = do_ref[0].astype(jnp.float32)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0]) * scale
    dk_scr[...] += jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_bhsd(q, k, v, o, lse, do, q_offset, *, scale, causal, kv_len,
                    block_q, block_k, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True)                 # [bh, sq_pad, 1]

    common = dict(scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, kv_len=kv_len)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    rowspec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            qspec,
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            qspec, rowspec, rowspec,
        ],
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q_offset, q, k, v, do, lse, delta)[0]

    # dk/dv: grid walks k blocks outer, q blocks inner.
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    qspec2 = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    rowspec2 = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            qspec2, kspec, kspec, qspec2, rowspec2, rowspec2,
        ],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, sk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q_offset, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------- public API

def _pad_seq(x, block):
    s = x.shape[1]
    pad = (-s) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _prep(q, k, v):
    """[b,s,h,d] -> [b*h, s, d] with GQA kv-head repetition."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    if hq != hkv:
        if hq % hkv:
            raise ValueError(f"q heads {hq} not divisible by kv heads {hkv}")
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    to_bhsd = lambda x: x.transpose(0, 2, 1, 3).reshape(b * hq, x.shape[1], d)
    return to_bhsd(q), to_bhsd(k), to_bhsd(v)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_block(requested: int, seq: int) -> int:
    """Block size: the requested one, shrunk (to a multiple of 8) for short
    sequences so tiny shapes don't pad to 128."""
    return min(requested, _round_up(max(seq, 8), 8))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, scale, causal, block_q, block_k, interpret, q_offset):
    return _flash_core_fwd(q, k, v, scale, causal, block_q, block_k,
                           interpret, q_offset)[0]


def _flash_core_fwd(q, k, v, scale, causal, block_q, block_k, interpret,
                    q_offset):
    qoff = jnp.asarray([q_offset], jnp.int32)
    sq, sk = q.shape[1], k.shape[1]
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    o, lse = _flash_fwd_bhsd(qp, kp, vp, qoff, scale=scale, causal=causal,
                             kv_len=sk, block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return o[:, :sq], (q, k, v, o, lse)


def _flash_core_bwd(scale, causal, block_q, block_k, interpret, q_offset,
                    res, do):
    q, k, v, o_pad, lse = res
    qoff = jnp.asarray([q_offset], jnp.int32)
    sq, sk = q.shape[1], k.shape[1]
    qp, kp, vp = _pad_seq(q, block_q), _pad_seq(k, block_k), _pad_seq(v, block_k)
    dop = _pad_seq(do, block_q)
    dq, dk, dv = _flash_bwd_bhsd(qp, kp, vp, o_pad, lse, dop, qoff,
                                 scale=scale, causal=causal, kv_len=sk,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    q_offset: int = 0,
                    block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable flash attention over [batch, seq, heads, head_dim].

    Drop-in for ``ray_tpu.ops.attention.mha`` (minus segment_ids/bias — the
    XLA path handles those). ``q_offset``: absolute position of q[0] relative
    to k[0], for decode and ring steps; static here (see
    ``flash_attention_with_lse`` for a traced offset).
    """
    b, sq, hq, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _needs_interpret()
    block_q = _pick_block(block_q, sq)
    block_k = _pick_block(block_k, k.shape[1])
    qf, kf, vf = _prep(q, k, v)
    o = _flash_core(qf, kf, vf, scale, causal, block_q, block_k, interpret,
                    q_offset)
    return o.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)


def flash_vjp_chunk(q, k, v, o, do, lse, *,
                    q_offset,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None):
    """Per-chunk backward for ring attention.

    Given the GLOBAL (o, lse) of the softmax over all chunks and one k/v
    chunk, returns this chunk's additive contribution (dq_partial, dk, dv).
    Summing dq_partial over chunks (and routing dk/dv home around the ring)
    yields exact gradients, because p = exp(s - lse_global) is the true
    softmax weight. q,k,v,o,do: [b,s,h,d]; lse: [b,h,s]; q_offset may be
    traced.
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _needs_interpret()
    block_q = _pick_block(block_q, sq)
    block_k = _pick_block(block_k, k.shape[1])
    sk = k.shape[1]
    qf, kf, vf = _prep(q, k, v)
    to_bhsd = lambda x: x.transpose(0, 2, 1, 3).reshape(b * hq, x.shape[1], d)
    of, dof = to_bhsd(o), to_bhsd(do)
    lsef = lse.reshape(b * hq, sq, 1)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)

    qp = _pad_seq(qf, block_q)
    kp, vp = _pad_seq(kf, block_k), _pad_seq(vf, block_k)
    op, dop = _pad_seq(of, block_q), _pad_seq(dof, block_q)
    lsep = jnp.pad(lsef, ((0, 0), (0, qp.shape[1] - sq), (0, 0)),
                   constant_values=NEG_INF)
    dq, dk, dv = _flash_bwd_bhsd(qp, kp, vp, op, lsep, dop, qoff,
                                 scale=scale, causal=causal, kv_len=sk,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    from_bhsd = lambda x, s_: x[:, :s_].reshape(b, hq, s_, d).transpose(0, 2, 1, 3)
    dq, dk, dv = from_bhsd(dq, sq), from_bhsd(dk, sk), from_bhsd(dv, sk)
    if hq != hkv:
        rep = hq // hkv
        dk = dk.reshape(b, sk, hkv, rep, d).sum(axis=3)
        dv = dv.reshape(b, sk, hkv, rep, d).sum(axis=3)
    return dq, dk, dv


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             q_offset=0,
                             block_q: int = 128,
                             block_k: int = 128,
                             interpret: Optional[bool] = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """(out [b,s,h,d], lse [b,h,s]) — the composable form for ring attention.

    Forward-only through the kernel (ring attention builds its VJP by
    recomputation); ``q_offset`` may be a traced scalar.
    """
    b, sq, hq, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _needs_interpret()
    block_q = _pick_block(block_q, sq)
    block_k = _pick_block(block_k, k.shape[1])
    qf, kf, vf = _prep(q, k, v)
    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    sk = kf.shape[1]
    qp, kp, vp = _pad_seq(qf, block_q), _pad_seq(kf, block_k), _pad_seq(vf, block_k)
    o, lse = _flash_fwd_bhsd(qp, kp, vp, qoff, scale=scale, causal=causal,
                             kv_len=sk, block_q=block_q, block_k=block_k,
                             interpret=interpret)
    o = o[:, :sq].reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
    lse = lse[:, :sq, 0].reshape(b, hq, sq)
    return o, lse
