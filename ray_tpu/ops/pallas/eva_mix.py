"""What an EVA layer does between its three projections and its attention
kernels (``ops/eva.eva_attention``, ``impl="pallas"``), each stream once
through HBM forward and once backward: a Pallas call reads the projections'
results as the products write them, ``[b, s, heads * d]``, and writes what
``ops/pallas/eva_attn.py`` reads, heads first and padded to whole windows::

    q~, k~ = rope(q), rope(k)                  interleaved pairs, float32
    alpha_m = softmax_m(phi_a . k~_m)          over a chunk's c positions
    ks_j = mu_a + sum_m alpha_m k~_m
    vs_j = sum_m alpha_m v_m

(``ops/eva.py``'s header; ``k~`` as rounded to the streams' dtype, which is
what the kernels read and the backward keeps). The backward call reads the
kernels' ``dq``, ``dk``, ``dv``, ``dks``, ``dvs``, the kept ``k~`` and ``v``
and ``phi``, recomputes ``alpha``, adds the pooling's cotangents into
``dk`` and ``dv``, rotates ``dq`` and the summed ``dk`` back and writes the
three cotangents ``[b, s, heads * d]`` where the weight-gradient products
read them; ``dphi`` and ``dmu`` gather over the row blocks in float32 and
are folded once outside.

A grid step holds ``_ROWS`` rows of every head, a stream's whole rows one
way and the same rows of each head the other (``[b, heads, s, d]``: the out
specs' index maps put a head's rows where the kernels read them, so no
stream is turned through HBM; XLA's forms of these passes were 65 ms of an
816 ms step, PERF.md section 5), and walks the heads: a head's ``d`` lanes
at an offset the loop computes, ``[rows, d]`` float32 from load to store.
The rotation: the tables lie a lane each, cos repeated a pair and sin
signed by parity, and a pair's partner comes by two lane rolls and a select
(no stride-2 slice). The pooling: a chunk of a 16-bit stream is one tile,
so ``[rows, d]`` as ``[rows / c, c, d]`` moves nothing and a chunk's max and
sums are reductions along the sublanes; its logits come off the MXU, which
is idle, ``k~`` against ``phi`` down every column, so that a row's logit
stands on every lane and nothing is summed along the lanes forward (bf16
operands and a float32 sum: exact). Backward the cotangent of ``alpha`` is
one such sum a row, and a chunk's ``dks``, ``dvs`` reach its rows as a
product with ones.

Rows past the sequence in a last window that is part full are written as
zeros (keys no real query sees; ``ks`` there is ``mu``), as ``jnp.pad`` made
them; their cotangents are not written.

The forward rule names its five results ``eva.RESIDUAL_NAMES`` and keeps of
them ``k~`` and ``v``, with ``phi`` and the tables: a remat block that saves
by those names (``llama.remat_block``) runs neither call nor any of the
three projections a second time.

Precision: float32 from a tile's load to its store, rounded once. Calls:
``eva_mix_<fwd|bwd>_s<seq>_h<heads>_d<d>_c<chunk>``, 1.29 and 1.70 ms at s
16,384 x 32 heads of 128 in bf16, ~630 GB/s of the streams they move (the
described-chip compile's schedule is under HBM's pace:
``tests/test_aot_tpu_compile.py``). On the chip ``d`` is whole lanes of 128
and a chunk whole tiles; on the CPU the calls run interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import eva
from ray_tpu.ops.pallas import flash

F32 = jnp.float32
#: rows a grid step holds of every head: whole chunks, a window a whole
#: number of them
_ROWS = 256
_WHOLE = pl.BlockSpec(memory_space=pltpu.VMEM)


def _rows(window: int, chunk: int) -> int:
    """Rows a block: the most within ``_ROWS`` that are whole chunks and
    divide a window."""
    return max(r for r in range(chunk, min(_ROWS, window) + 1, chunk)
               if window % r == 0)


def _rotate(x, cos, sin, even, back: bool = False):
    """The interleaved pairs of ``x`` [rows, d] turned by the tables' angle
    (``back``: by its negative, the transpose). ``sin`` is signed by parity,
    minus on the even lanes, so ``y = x cos + partner sin`` is both
    ``x1 c - x2 s`` and ``x1 s + x2 c``."""
    d = x.shape[-1]
    partner = jnp.where(even, pltpu.roll(x, d - 1, 1), pltpu.roll(x, 1, 1))
    return x * cos - partner * sin if back else x * cos + partner * sin


def _dot(a, b, dims):
    """A product on the MXU that is exact: one operand ones or ``phi`` and
    both the streams' dtype, the sum float32 (a float32 stream's at
    ``highest``)."""
    exact = jax.lax.Precision.HIGHEST if a.dtype == F32 else None
    return jax.lax.dot_general(a, b, dims, precision=exact,
                               preferred_element_type=F32)


def _logits(k, phi):
    """``phi . k`` a row of ``k`` [rows, d], on every lane of the row: the
    product against ``phi`` [1, d] down d rows, so that no sum along the
    lanes has to be spread back over them."""
    d = k.shape[1]
    return _dot(k, jnp.broadcast_to(phi.astype(k.dtype), (d, d)), flash._NT)


def _weights(logits, chunk: int):
    """Of its positions' logits [rows, d] (a row's on every lane) a chunk's
    exp(l - max), [rows / chunk, chunk, d]: the softmax before it is
    divided by its sum."""
    logits = _by_chunk(logits, chunk)
    return jnp.exp(logits - jnp.max(logits, axis=1, keepdims=True))


def _by_chunk(x, chunk: int):
    """[rows, d] -> [rows / chunk, chunk, d]: whole tiles a chunk, nothing
    moves."""
    return x.reshape(x.shape[0] // chunk, chunk, x.shape[1])


def _fwd_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, phi_ref, mu_ref,
                qo_ref, ko_ref, vo_ref, ks_ref, vs_ref, *, chunk: int,
                seq: int, ragged: bool):
    """``ragged``: the grid goes past the sequence's ``seq`` rows, and a
    block's rows at or past them (which hold anything) are written as
    zeros."""
    heads, rows, d = qo_ref.shape
    even = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 1) % 2 == 0
    keep = lambda x: x  # noqa: E731
    if ragged:
        live = (pl.program_id(1) * rows
                + jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0)) < seq
        keep = lambda x: jnp.where(live, x, 0.0)  # noqa: E731

    def head(a, carry):
        lanes = pl.ds(pl.multiple_of(a * d, d), d)
        cos, sin = cos_ref[...], sin_ref[...]
        qo_ref[a] = keep(_rotate(q_ref[:, lanes].astype(F32), cos, sin, even)
                         ).astype(qo_ref.dtype)
        k = keep(_rotate(k_ref[:, lanes].astype(F32), cos, sin, even)
                 ).astype(ko_ref.dtype)
        v = keep(v_ref[:, lanes])
        ko_ref[a], vo_ref[a] = k, v
        e = _weights(_logits(k, phi_ref[a]), chunk)
        inv = 1.0 / jnp.sum(e, axis=1)                     # [rows / chunk, d]
        ks = jnp.sum(e * _by_chunk(k.astype(F32), chunk), axis=1)
        vs = jnp.sum(e * _by_chunk(v.astype(F32), chunk), axis=1)
        ks_ref[a] = (mu_ref[a] + inv * ks).astype(ks_ref.dtype)
        vs_ref[a] = (inv * vs).astype(vs_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def _bwd_kernel(dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref, k_ref, v_ref,
                cos_ref, sin_ref, phi_ref, dqo_ref, dko_ref, dvo_ref,
                dphi_ref, dmu_ref, *, chunk: int):
    heads, rows, d = k_ref.shape
    even = jax.lax.broadcasted_iota(jnp.int32, (rows, d), 1) % 2 == 0
    # [rows, rows / chunk] of ones where the row lies in the chunk: a chunk's
    # cotangent to each of its rows as a product (exact: ones and the
    # streams' dtype)
    to_rows = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, rows // chunk), 0) // chunk
        == jax.lax.broadcasted_iota(jnp.int32, (rows, rows // chunk), 1)
    ).astype(dks_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, F32)
        dmu_ref[...] = jnp.zeros(dmu_ref.shape, F32)

    def head(a, carry):
        lanes = pl.ds(pl.multiple_of(a * d, d), d)
        cos, sin = cos_ref[...], sin_ref[...]
        k, v = k_ref[a].astype(F32), v_ref[a].astype(F32)
        dks = _dot(to_rows, dks_ref[a], flash._NN)         # [rows, d]
        dvs = _dot(to_rows, dvs_ref[a], flash._NN)
        e = _weights(_logits(k_ref[a], phi_ref[a]), chunk)
        alpha = e / jnp.sum(e, axis=1, keepdims=True)
        # alpha's cotangent a position, less its mean under alpha
        g = _by_chunk(jnp.broadcast_to(jnp.sum(
            dks * k + dvs * v, axis=-1, keepdims=True), (rows, d)), chunk)
        dlogit = (alpha * (g - jnp.sum(alpha * g, axis=1, keepdims=True))
                  ).reshape(rows, d)
        alpha = alpha.reshape(rows, d)
        dphi_ref[:, lanes] += jnp.sum(_by_chunk(dlogit * k, chunk), axis=0)
        dmu_ref[:, lanes] += dks_ref[a].astype(F32)
        dk = dk_ref[a].astype(F32) + alpha * dks + dlogit * phi_ref[a]
        dko_ref[:, lanes] = _rotate(dk, cos, sin, even, back=True
                                    ).astype(dko_ref.dtype)
        dqo_ref[:, lanes] = _rotate(dq_ref[a].astype(F32), cos, sin, even,
                                    back=True).astype(dqo_ref.dtype)
        dvo_ref[:, lanes] = (dv_ref[a].astype(F32) + alpha * dvs
                             ).astype(dvo_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


# ---------------------------------------------------------------- the calls

def _specs(seq: int, heads: int, d: int, window: int, chunk: int):
    """The blocks of one shape, ``rows`` rows a grid step (batch, row
    blocks) of the sequence as ``padded`` to whole windows: of a stream [b,
    s, heads * d] its rows of every head (a block wholly past the sequence
    reads the last one that is not: nothing is fetched), of the tables the
    same rows, of a stream heads first [b, heads, padded, d] and of the
    summaries [b, heads, padded / chunk, d] every head's."""
    padded, rows = -(-seq // window) * window, _rows(window, chunk)
    last = (seq - 1) // rows
    return dict(
        padded=padded, rows=rows, tag=f"s{seq}_h{heads}_d{d}_c{chunk}",
        stream=pl.BlockSpec((None, rows, heads * d),
                            lambda b, r: (b, jnp.minimum(r, last), 0)),
        table=pl.BlockSpec((rows, d), lambda b, r: (jnp.minimum(r, last), 0)),
        first=pl.BlockSpec((None, heads, rows, d), lambda b, r: (b, 0, r, 0)),
        pooled=pl.BlockSpec((None, heads, rows // chunk, d),
                            lambda b, r: (b, 0, r, 0)),
        sums=lambda n: pl.BlockSpec((None, n, heads * d),
                                    lambda b, r: (b, 0, 0)))


def _params(sums: bool, blocks: int, rows: int, lanes: int, itemsize: int):
    """``blocks`` stream blocks in flight twice over, and room to spare;
    ``sums``: the call gathers sums over the row blocks in a block it keeps,
    so they are walked in order."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary" if sums else "parallel"),
        vmem_limit_bytes=max(flash._VMEM_DEFAULT_LIMIT_BYTES,
                             3 * blocks * rows * lanes * itemsize + (8 << 20)))


# Jitted, as ``kda_mix``'s calls are: a step holds each once a layer and
# traces and lowers a body once.

@functools.partial(jax.jit,
                   static_argnames=("window", "chunk", "interpret"))
def _fwd_call(q, k, v, cos, sin, phi, mu, *, window: int, chunk: int,
              interpret: bool):
    batch, seq, ch = q.shape
    heads, d = phi.shape
    sp = _specs(seq, heads, d, window, chunk)
    padded, rows = sp["padded"], sp["rows"]
    first = jax.ShapeDtypeStruct((batch, heads, padded, d), q.dtype)
    pooled = jax.ShapeDtypeStruct((batch, heads, padded // chunk, d), q.dtype)
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, seq=seq,
                          ragged=padded != seq),
        grid=(batch, padded // rows),
        in_specs=[sp["stream"]] * 3 + [sp["table"]] * 2 + [_WHOLE] * 2,
        out_specs=[sp["first"]] * 3 + [sp["pooled"]] * 2,
        out_shape=[first] * 3 + [pooled] * 2,
        compiler_params=_params(False, 6, rows, ch, q.dtype.itemsize),
        interpret=interpret, name=f"eva_mix_fwd_{sp['tag']}",
    )(q, k, v, cos, sin, phi.astype(F32)[:, None], mu.astype(F32)[:, None])
    return [a.reshape(batch * heads, -1, d) for a in outs]


@functools.partial(jax.jit,
                   static_argnames=("seq", "window", "chunk", "interpret"))
def _bwd_call(dq, dk, dv, dks, dvs, k, v, cos, sin, phi, *, seq: int,
              window: int, chunk: int, interpret: bool):
    heads, d = phi.shape
    batch, ch = k.shape[0] // heads, heads * d
    sp = _specs(seq, heads, d, window, chunk)
    rows = sp["rows"]
    raw = jax.ShapeDtypeStruct((batch, seq, ch), k.dtype)
    *raws, dphi, dmu = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(batch, pl.cdiv(seq, rows)),
        in_specs=([sp["first"]] * 3 + [sp["pooled"]] * 2 + [sp["first"]] * 2
                  + [sp["table"]] * 2 + [_WHOLE]),
        out_specs=[sp["stream"]] * 3 + [sp["sums"](chunk),
                                        sp["sums"](rows // chunk)],
        out_shape=[raw] * 3 + [
            jax.ShapeDtypeStruct((batch, chunk, ch), F32),
            jax.ShapeDtypeStruct((batch, rows // chunk, ch), F32)],
        compiler_params=_params(True, 8, rows, ch, k.dtype.itemsize),
        interpret=interpret, name=f"eva_mix_bwd_{sp['tag']}",
    )(*(a.reshape(batch, heads, -1, d) for a in (dq, dk, dv, dks, dvs, k, v)),
      cos, sin, phi.astype(F32)[:, None])
    return (*raws, dphi.sum((0, 1)).reshape(heads, d),
            dmu.sum((0, 1)).reshape(heads, d))


def _lanes(sin, cos):
    """``rope_angles``' tables [s, d / 2] as the kernels read them, [s, d]
    float32 a lane each: cos repeated a pair, sin signed by parity."""
    cos = jnp.repeat(cos.astype(F32), 2, axis=-1)
    return cos, jnp.stack([-sin, sin], axis=-1).astype(F32).reshape(cos.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def mix(q: jax.Array, k: jax.Array, v: jax.Array, sin: jax.Array,
        cos: jax.Array, phi: jax.Array, mu: jax.Array, window: int,
        chunk: int):
    """``q``, ``k``, ``v`` [b, s, heads * d], three projections' results;
    ``sin``, ``cos`` [>= s, d / 2] (``ops/rope.rope_angles``); ``phi``,
    ``mu`` [heads, d]; ``s`` whole chunks, ``window`` whole chunks. Returns
    (q~, k~, v) [b * heads, s padded to whole windows, d] and (ks, vs) [b *
    heads, padded s / chunk, d] in the streams' dtype (module docstring).
    Differentiable in ``q``, ``k``, ``v``, ``phi`` and ``mu``."""
    return _mix_fwd(q, k, v, sin, cos, phi, mu, window, chunk)[0]


def _mix_fwd(q, k, v, sin, cos, phi, mu, window, chunk):
    sin, cos = sin[:q.shape[1]], cos[:q.shape[1]]
    outs = _fwd_call(q, k, v, *_lanes(sin, cos), phi, mu, window=window,
                     chunk=chunk, interpret=flash._needs_interpret())
    outs = tuple(map(checkpoint_name, outs, eva.RESIDUAL_NAMES))
    return outs, (outs[1], outs[2], sin, cos, phi, mu)


def _mix_bwd(window, chunk, res, cotangents):
    k, v, sin, cos, phi, mu = res
    *raw, dphi, dmu = _bwd_call(
        *cotangents, k, v, *_lanes(sin, cos), phi, seq=sin.shape[0],
        window=window, chunk=chunk,
        interpret=flash._needs_interpret())
    return (*raw, None, None, dphi.astype(phi.dtype), dmu.astype(mu.dtype))


mix.defvjp(_mix_fwd, _mix_bwd)
