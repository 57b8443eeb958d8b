"""The elementwise chains of a ``kda`` layer outside its recurrence
(``models/mixers.kda_half``), each one pass over its stream forward and one
backward: a Pallas call reads a ``[rows, lanes]`` block of whole heads once,
works it in float32 in VMEM and writes it once in the stream's dtype.

``conv_silu_unit``: the short causal convolution of a projection's result,
its SiLU and (q and k) a head's L2 norm::

    c_t = sum_j taps_j p_{t - (K - 1) + j}            (p before a sequence: 0)
    a = c * sigmoid(c)
    y = a * rsqrt(sum_head a^2 + eps) * scale         (``unit``; else y = a)

``decay``: the log-decay a channel, float32::

    g = rate * softplus(lin + bias)                   (rate = -exp(A_log))

``norm_gate``: the output's RMSNorm over each head's width times its gate::

    y = o * rsqrt(mean_head o^2 + eps) * weight * sigmoid(gate + bias)

XLA lays a reduction over a head's 128 channels out as ``[s, heads]``, the
heads on the lanes, and writes its spread back over the channels to HBM,
forward, recomputed and backward; on a tile of one head's lanes the sum is
an XLU's and its spread a vreg's layout (PERF.md section 6, PR 51). The rows
a block's first rows read of the block before (and, backward, a block's
last rows of the cotangent after it) come in as a second, ``_HALO``-row
block of the same array, so no shifted copy is ever written to HBM: the
window lies in a float32 scratch as slabs of ``8 + _SUB`` rows and a tap
reads a slab at its own, static, sublane offset (Mosaic proves no alignment
of an offset a loop computes).

The recurrence works heads first (``kda_chunked`` turns its arguments to
[b, h, chunks, C, w] and its result back), so a stream it reads or writes
crosses this module heads first, [b, heads, s, w]: a call writes or reads a
head's rows where the recurrence wants them, the caller's ``moveaxis`` meets
``kda_chunked``'s inverse, and no stream is transposed through HBM between
the two. What a product makes or reads (``p``, the gates, the output on its
way to ``wo``) is [b, s, heads * w].

A backward call recomputes its forward in VMEM from the chain's inputs,
which the remat policy keeps (they are products' results), so a chain keeps
nothing of its own, and a remat block runs a forward call a second time on
its way to the backward. Sums over the rows (the cotangents of the taps,
the biases, the decay's rate and the norm's weight) are accumulated across
the row blocks as eight sublanes a lane, float32, and folded once outside.

Precision: a tile is float32 from its load to its store, rounded once; the
sigmoid is ``(1 + tanh(x / 2)) / 2``, one transcendental.

Calls are named ``kda_mix_<conv|conv_unit|decay|norm_gate>_<fwd|bwd>_s<seq>
_h<heads>_w<width>`` so that a device trace shows them. On the CPU they run
interpreted (``flash._needs_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kda
from ray_tpu.ops.pallas import flash

F32 = jnp.float32
#: rows of the block that brings a block's neighbouring rows: a tile of a
#: 16-bit dtype, of which the eight nearest are read (``kda.MAX_CONV_TAPS``)
_HALO = 16
#: rows and lanes a grid step holds at most, and rows worked at once (a
#: head's lanes of them: eight float32 vregs an array at 128). Chosen on
#: the chip at the benchmark's shape (s 16,384, 32 heads x 128, bf16; ms a
#: call forward / backward, my chip runs, PR 51): the L2-normed convolution
#: 0.81 / 1.26 at 512 x 512 x 32, 0.56 / 0.99 at 512 x 1024 x 32, **0.51 /
#: 0.93 at 512 x 1024 x 64**, 0.84 / 1.30 at 512 x 1024 x 16, 0.50 / 0.95
#: at 512 x 2048 x 32 (which compiles twice as long); the other three pairs
#: move by under a tenth. A loop's trip ends on its sums along the lanes
#: and the roots that wait for them: the more heads and rows a trip holds,
#: the less of it is that wait
_ROWS, _LANES, _SUB = 512, 1024, 64


def _tiles(seq: int, heads: int, w: int):
    """(rows a block, heads a block) at one shape: whole ``_SUB``s up to
    ``_ROWS`` and no more than the sequence where it has that many, and the
    most heads that divide ``heads`` within ``_LANES``."""
    rows = min(_ROWS, max(seq // _SUB, 1) * _SUB)
    hs = max(d for d in range(1, heads + 1)
             if heads % d == 0 and (d == 1 or d * w <= _LANES))
    return rows, hs


def _fold(x):
    """[n, w] -> [8, w]: the rows summed eight sublanes apart (vreg adds)."""
    return functools.reduce(
        jnp.add, [x[at:at + 8] for at in range(0, x.shape[0], 8)])


def _keep(x, first_row, seq: int, ragged: bool):
    """``x`` [n, w] with zeros in the rows at or past the sequence's end
    (what a last block that is not whole holds there is anything)."""
    if not ragged:
        return x
    row = first_row + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(row < seq, x, 0.0)


def _sigmoid(x):
    """Through ``tanh``: one transcendental where ``1 / (1 + exp(-x))`` is
    two."""
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


# ------------------------------------------------ convolution, SiLU, L2 norm

def _conv(win_s, taps_ref, i, n: int, lanes):
    """The convolution's first ``n`` rows of slab ``i``: tap ``j`` against
    the window's rows ``t - (K - 1) + j``, a load at a sublane offset that
    is static; the token's own tap first, whose rows lie on whole tiles (the
    sum takes its layout)."""
    K = taps_ref.shape[0]
    return functools.reduce(jnp.add, [
        taps_ref[j:j + 1, lanes] * win_s[i, pl.ds(8 - (K - 1) + j, n), lanes]
        for j in reversed(range(K))])


def _fill(win_s, prev_ref, p_ref, keep):
    """The window, float32, as slabs of ``8 + _SUB`` rows: slab ``i`` holds
    the block's rows ``i * _SUB - 8 .. (i + 1) * _SUB`` (the eight before
    the block are the block before's, zeros before a sequence), so that a
    tap's rows lie at a static offset in a slab a loop picks."""
    rows = p_ref.shape[0]
    win_s[0, 0:8, :] = jnp.where(pl.program_id(2) == 0, 0.0,
                                 prev_ref[8:, :].astype(F32))

    def fill(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        x = keep(p_ref[pl.ds(at, _SUB), :].astype(F32), at)
        win_s[i, 8:, :] = x
        win_s[i + 1, 0:8, :] = x[_SUB - 8:]
        return carry

    jax.lax.fori_loop(0, rows // _SUB, fill, 0)


def _conv_fwd_kernel(scale_ref, prev_ref, p_ref, taps_ref, y_ref, win_s, *,
                     w: int, unit: bool, eps: float):
    rows, lanes = p_ref.shape
    _fill(win_s, prev_ref, p_ref, lambda x, at: x)
    scale = scale_ref[0, 0]

    def tile(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        for hd in range(lanes // w):
            head = slice(hd * w, (hd + 1) * w)
            c = _conv(win_s, taps_ref, i, _SUB, head)
            a = c * _sigmoid(c)
            if unit:
                a = a * (jax.lax.rsqrt(
                    jnp.sum(a * a, axis=-1, keepdims=True) + eps) * scale)
            y_ref[hd, pl.ds(at, _SUB), :] = a.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // _SUB, tile, 0)


def _conv_bwd_kernel(scale_ref, prev_ref, p_ref, next_ref, taps_ref, dy_ref,
                     dyn_ref, dp_ref, dtaps_ref, win_s, dc_s, *, w: int,
                     unit: bool, eps: float, seq: int):
    """``dc_s``, slabs of ``_SUB + 8`` rows: slab ``i + 1`` holds the
    cotangent of the convolution's rows ``i * _SUB .. (i + 1) * _SUB + 8``,
    of which the transposed convolution reads the ``K - 1`` that follow a
    row; the eight after the block are recomputed here from the block
    after's rows, not exchanged."""
    rows, lanes = p_ref.shape
    K, tiles = taps_ref.shape[0], rows // _SUB
    r, last = pl.program_id(2), pl.program_id(2) == pl.num_programs(2) - 1
    ragged = seq % rows != 0
    keep = lambda x, at: _keep(x, r * rows + at, seq, ragged)  # noqa: E731

    @pl.when((pl.program_id(1) == 0) & (r == 0))
    def _():
        dtaps_ref[...] = jnp.zeros(dtaps_ref.shape, F32)

    _fill(win_s, prev_ref, p_ref, keep)
    win_s[tiles, 8:16, :] = keep(next_ref[0:8, :].astype(F32), rows)
    scale = scale_ref[0, 0]

    def pulled(i, at, n, dy, head):
        """The cotangent of the convolution's first ``n`` rows of slab ``i``
        (the block's from ``at``) from their result's ``dy`` [n, w]."""
        c = _conv(win_s, taps_ref, i, n, head)
        sig = _sigmoid(c)
        a = c * sig
        if unit:
            inv = jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + eps)
            along = jnp.sum(dy * a, axis=-1, keepdims=True) * (inv * inv)
            dy = (dy - a * along) * (inv * scale)
        return keep(dy * (sig + a * (1.0 - sig)), at)

    def pull(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        for hd in range(lanes // w):
            head = slice(hd * w, (hd + 1) * w)
            dc = pulled(i, at, _SUB,
                        dy_ref[hd, pl.ds(at, _SUB), :].astype(F32), head)
            dc_s[i + 1, 0:_SUB, head] = dc
            dc_s[i, _SUB:, head] = dc[0:8]
        return carry

    jax.lax.fori_loop(0, tiles, pull, 0)
    for hd in range(lanes // w):
        head = slice(hd * w, (hd + 1) * w)
        dc_s[tiles, _SUB:, head] = jnp.where(last, 0.0, pulled(
            tiles, rows, 8, dyn_ref[hd, 0:8, :].astype(F32), head))

    def transposed(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        for lo in range(0, lanes, w):
            head = slice(lo, lo + w)
            ahead = [dc_s[i + 1, pl.ds((K - 1) - j, _SUB), head]
                     for j in range(K)]
            dp_ref[pl.ds(at, _SUB), head] = functools.reduce(jnp.add, [
                taps_ref[j:j + 1, head] * ahead[j]
                for j in reversed(range(K))]).astype(dp_ref.dtype)
            # a tap's cotangent, sum_t dc_t p_{t - (K - 1) + j}, gathered by
            # the row of ``p``: the block's own rows against the rows the
            # transposed convolution has just read
            for j in range(K):
                dtaps_ref[j, :, head] += _fold(win_s[i, 8:, head] * ahead[j])
        return carry

    jax.lax.fori_loop(0, tiles, transposed, 0)


# ---------------------------------------------------- RMSNorm a head and gate

def _gate_fwd_kernel(o_ref, weight_ref, gate_ref, bias_ref, y_ref, *, w: int,
                     eps: float):
    rows, lanes = gate_ref.shape

    def tile(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        for hd in range(lanes // w):
            head = slice(hd * w, (hd + 1) * w)
            o = o_ref[hd, pl.ds(at, _SUB), :].astype(F32)
            sig = _sigmoid(gate_ref[pl.ds(at, _SUB), head].astype(F32)
                           + bias_ref[:, head])
            inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            y_ref[pl.ds(at, _SUB), head] = (
                o * inv * weight_ref[...] * sig).astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // _SUB, tile, 0)


def _gate_bwd_kernel(o_ref, weight_ref, gate_ref, bias_ref, dy_ref, do_ref,
                     dgate_ref, dweight_ref, dbias_ref, *, w: int, eps: float,
                     seq: int):
    rows, lanes = gate_ref.shape
    r = pl.program_id(2)
    ragged = seq % rows != 0

    @pl.when((pl.program_id(1) == 0) & (r == 0))
    def _():
        dweight_ref[...] = jnp.zeros(dweight_ref.shape, F32)
        dbias_ref[...] = jnp.zeros(dbias_ref.shape, F32)

    def tile(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        keep = lambda x: _keep(x, r * rows + at, seq, ragged)  # noqa: E731
        for hd in range(lanes // w):
            head = slice(hd * w, (hd + 1) * w)
            o = keep(o_ref[hd, pl.ds(at, _SUB), :].astype(F32))
            dy = keep(dy_ref[pl.ds(at, _SUB), head].astype(F32))
            sig = _sigmoid(keep(gate_ref[pl.ds(at, _SUB), head].astype(F32))
                           + bias_ref[:, head])
            inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
            normed = o * inv
            dgated = dy * sig                       # what the gate lets through
            dgate = dgated * normed * weight_ref[...] * (1.0 - sig)
            dnormed = dgated * weight_ref[...]
            do_ref[hd, pl.ds(at, _SUB), :] = ((dnormed - normed * jnp.mean(
                dnormed * normed, axis=-1, keepdims=True)) * inv
                ).astype(do_ref.dtype)
            dgate_ref[pl.ds(at, _SUB), head] = dgate.astype(dgate_ref.dtype)
            dweight_ref[:, head] += _fold(dgated * normed)
            dbias_ref[:, head] += _fold(dgate)
        return carry

    jax.lax.fori_loop(0, rows // _SUB, tile, 0)


# ----------------------------------------------------- the decay a channel

def _softplus(x):
    """``log(1 + exp(x))`` as ``jax.nn.softplus`` writes it."""
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _decay_fwd_kernel(lin_ref, bias_ref, rate_ref, g_ref, *, w: int):
    rows, lanes = lin_ref.shape

    def tile(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        for hd in range(lanes // w):
            head = slice(hd * w, (hd + 1) * w)
            g_ref[hd, pl.ds(at, _SUB), :] = rate_ref[:, head] * _softplus(
                lin_ref[pl.ds(at, _SUB), head].astype(F32) + bias_ref[:, head])
        return carry

    jax.lax.fori_loop(0, rows // _SUB, tile, 0)


def _decay_bwd_kernel(lin_ref, bias_ref, rate_ref, dg_ref, dlin_ref, dbias_ref,
                      drate_ref, *, w: int, seq: int):
    rows, lanes = lin_ref.shape
    r = pl.program_id(2)
    ragged = seq % rows != 0

    @pl.when((pl.program_id(1) == 0) & (r == 0))
    def _():
        dbias_ref[...] = jnp.zeros(dbias_ref.shape, F32)
        drate_ref[...] = jnp.zeros(drate_ref.shape, F32)

    def tile(i, carry):
        at = pl.multiple_of(i * _SUB, _SUB)
        keep = lambda x: _keep(x, r * rows + at, seq, ragged)  # noqa: E731
        for hd in range(lanes // w):
            head = slice(hd * w, (hd + 1) * w)
            x = keep(lin_ref[pl.ds(at, _SUB), head].astype(F32)) \
                + bias_ref[:, head]
            dg = keep(dg_ref[hd, pl.ds(at, _SUB), :])
            dlin = dg * rate_ref[:, head] * _sigmoid(x)
            dlin_ref[pl.ds(at, _SUB), head] = dlin.astype(dlin_ref.dtype)
            dbias_ref[:, head] += _fold(dlin)
            drate_ref[:, head] += _fold(dg * _softplus(x))
        return carry

    jax.lax.fori_loop(0, rows // _SUB, tile, 0)


# ---------------------------------------------------------------- the calls

def _specs(seq: int, heads: int, w: int):
    """The grid (lane slabs, batch, row blocks: the rows innermost, so that
    a sum over rows stays in VMEM while it gathers) and the kinds of block:
    of a stream [b, s, heads * w] its [rows, lanes], the ``_HALO`` rows
    before it and after it; of a stream heads first, [b, heads, s, w], the
    same rows of the same heads, [heads a block, rows, w], and the rows
    after; and a row of ``n`` sublanes a lane ([n, heads * w] arrays)."""
    rows, hs = _tiles(seq, heads, w)
    lanes, per, halos = hs * w, rows // _HALO, pl.cdiv(seq, _HALO)
    vmem = pltpu.VMEM

    def a_row(*lead):
        return pl.BlockSpec((*lead, lanes),
                            lambda l, b, r: (*(0,) * len(lead), l),
                            memory_space=vmem)

    return dict(
        rows=rows, lanes=lanes, tag=f"s{seq}_h{heads}_w{w}",
        grid=(heads // hs, pl.cdiv(seq, rows)),
        block=pl.BlockSpec((None, rows, lanes), lambda l, b, r: (b, r, l),
                           memory_space=vmem),
        before=pl.BlockSpec(
            (None, _HALO, lanes),
            lambda l, b, r: (b, jnp.maximum(r * per - 1, 0), l),
            memory_space=vmem),
        after=pl.BlockSpec(
            (None, _HALO, lanes),
            lambda l, b, r: (b, jnp.minimum((r + 1) * per, halos - 1), l),
            memory_space=vmem),
        heads=pl.BlockSpec((None, hs, rows, w), lambda l, b, r: (b, l, r, 0),
                           memory_space=vmem),
        heads_after=pl.BlockSpec(
            (None, hs, _HALO, w),
            lambda l, b, r: (b, l, jnp.minimum((r + 1) * per, halos - 1), 0),
            memory_space=vmem),
        a_row=a_row,
        scalar=pl.BlockSpec(memory_space=pltpu.SMEM))


def _slabs(sp):
    """A block's rows and eight of a neighbour's as float32 slabs (``_fill``,
    ``_conv_bwd_kernel``), and one slab more for the block after's."""
    return pltpu.VMEM((sp["rows"] // _SUB + 1, 8 + _SUB, sp["lanes"]), F32)


def _call(sp, batch: int, kernel, what: str, in_specs, out_specs, out_shape,
          *, interpret: bool, scratch=(), sums: bool = False):
    """The ``pallas_call`` of one kernel over ``_specs``' grid. ``sums``: the
    call gathers sums over the rows in a block it keeps, so the batch and
    the row blocks are walked in order."""
    slabs, blocks = sp["grid"]
    inner = ("arbitrary" if sums else "parallel",) * 2
    return pl.pallas_call(
        kernel, grid=(slabs, batch, blocks), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", *inner)),
        interpret=interpret, name=f"kda_mix_{what}_{sp['tag']}")


# Jitted, as ``kda_insides``' calls are: a step holds each several times (q, k
# and v, a layer's forward and its recomputation, every ``kda`` layer) and
# traces and lowers a body once.

@functools.partial(jax.jit, static_argnames=("w", "unit", "eps", "interpret"))
def _conv_fwd_call(scale, p, taps, *, w: int, unit: bool, eps: float,
                   interpret: bool):
    batch, seq, ch = p.shape
    sp = _specs(seq, ch // w, w)
    return _call(
        sp, batch, functools.partial(_conv_fwd_kernel, w=w, unit=unit, eps=eps),
        f"conv{'_unit' * unit}_fwd",
        [sp["scalar"], sp["before"], sp["block"], sp["a_row"](taps.shape[0])],
        sp["heads"], jax.ShapeDtypeStruct((batch, ch // w, seq, w), p.dtype),
        scratch=[_slabs(sp)], interpret=interpret)(scale, p, p, taps)


@functools.partial(jax.jit, static_argnames=("w", "unit", "eps", "interpret"))
def _conv_bwd_call(scale, p, taps, dy, *, w: int, unit: bool, eps: float,
                   interpret: bool):
    batch, seq, ch = p.shape
    K = taps.shape[0]
    sp = _specs(seq, ch // w, w)
    return _call(
        sp, batch,
        functools.partial(_conv_bwd_kernel, w=w, unit=unit, eps=eps, seq=seq),
        f"conv{'_unit' * unit}_bwd",
        [sp["scalar"], sp["before"], sp["block"], sp["after"], sp["a_row"](K),
         sp["heads"], sp["heads_after"]],
        [sp["block"], sp["a_row"](K, 8)],
        [jax.ShapeDtypeStruct(p.shape, p.dtype),
         jax.ShapeDtypeStruct((K, 8, ch), F32)],
        scratch=[_slabs(sp), _slabs(sp)], sums=True, interpret=interpret,
    )(scale, p, p, p, taps, dy, dy)


_WHOLE = pl.BlockSpec(memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _gate_fwd_call(o, weight, gate, bias, *, eps: float, interpret: bool):
    batch, seq, ch = gate.shape
    w = weight.shape[-1]
    sp = _specs(seq, ch // w, w)
    return _call(
        sp, batch, functools.partial(_gate_fwd_kernel, w=w, eps=eps),
        "norm_gate_fwd", [sp["heads"], _WHOLE, sp["block"], sp["a_row"](1)],
        sp["block"], jax.ShapeDtypeStruct(gate.shape, o.dtype),
        interpret=interpret)(o, weight, gate, bias)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _gate_bwd_call(o, weight, gate, bias, dy, *, eps: float, interpret: bool):
    batch, seq, ch = gate.shape
    w = weight.shape[-1]
    sp = _specs(seq, ch // w, w)
    sums = jax.ShapeDtypeStruct((8, ch), F32)
    return _call(
        sp, batch, functools.partial(_gate_bwd_kernel, w=w, eps=eps, seq=seq),
        "norm_gate_bwd",
        [sp["heads"], _WHOLE, sp["block"], sp["a_row"](1), sp["block"]],
        [sp["heads"], sp["block"], sp["a_row"](8), sp["a_row"](8)],
        [jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct(gate.shape, gate.dtype), sums, sums],
        sums=True, interpret=interpret)(o, weight, gate, bias, dy)


@functools.partial(jax.jit, static_argnames=("w", "interpret"))
def _decay_fwd_call(lin, bias, rate, *, w: int, interpret: bool):
    batch, seq, ch = lin.shape
    sp = _specs(seq, ch // w, w)
    return _call(
        sp, batch, functools.partial(_decay_fwd_kernel, w=w), "decay_fwd",
        [sp["block"], sp["a_row"](1), sp["a_row"](1)], sp["heads"],
        jax.ShapeDtypeStruct((batch, ch // w, seq, w), F32),
        interpret=interpret)(lin, bias, rate)


@functools.partial(jax.jit, static_argnames=("w", "interpret"))
def _decay_bwd_call(lin, bias, rate, dg, *, w: int, interpret: bool):
    batch, seq, ch = lin.shape
    sp = _specs(seq, ch // w, w)
    sums = jax.ShapeDtypeStruct((8, ch), F32)
    return _call(
        sp, batch, functools.partial(_decay_bwd_kernel, w=w, seq=seq),
        "decay_bwd",
        [sp["block"], sp["a_row"](1), sp["a_row"](1), sp["heads"]],
        [sp["block"], sp["a_row"](8), sp["a_row"](8)],
        [jax.ShapeDtypeStruct(lin.shape, lin.dtype), sums, sums],
        sums=True, interpret=interpret)(lin, bias, rate, dg)


def _scalar(x: float):
    return jnp.full((1, 1), x, F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def conv_silu_unit(p: jax.Array, taps: jax.Array, w: int, unit: bool,
                   scale: float = 1.0, eps: float = 0.0) -> jax.Array:
    """``p`` [b, s, heads * w], a projection's result; ``taps`` [K, heads *
    w], ``K <= kda.MAX_CONV_TAPS``, the depthwise causal convolution's, the
    last the token's own. Returns ``silu(conv(p))`` and, where ``unit``,
    each head's ``w`` channels over their L2 norm (``eps`` under the root)
    times ``scale``, heads first, [b, heads, s, w], in ``p``'s dtype (module
    docstring); ``w`` whole lanes of 128.
    ``scale`` is no part of a compiled call's key: q's and k's are one.
    Differentiable in ``p`` and ``taps``."""
    if taps.shape[0] > kda.MAX_CONV_TAPS:
        raise ValueError(f"{taps.shape[0]} taps: of the rows before a block "
                         f"the {kda.MAX_CONV_TAPS - 1} nearest are read")
    return _conv_fwd_call(_scalar(scale), p, taps.astype(F32), w=w, unit=unit,
                          eps=eps, interpret=flash._needs_interpret())


def _conv_vjp_fwd(p, taps, w, unit, scale, eps):
    return conv_silu_unit(p, taps, w, unit, scale, eps), (p, taps)


def _conv_vjp_bwd(w, unit, scale, eps, res, dy):
    p, taps = res
    dp, dtaps = _conv_bwd_call(
        _scalar(scale), p, taps.astype(F32), dy, w=w, unit=unit, eps=eps,
        interpret=flash._needs_interpret())
    return dp, dtaps.sum(axis=1).astype(taps.dtype)


conv_silu_unit.defvjp(_conv_vjp_fwd, _conv_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def norm_gate(o: jax.Array, weight: jax.Array, gate: jax.Array,
              bias: jax.Array, eps: float) -> jax.Array:
    """``o`` [b, heads, s, w], the recurrence's output, heads first;
    ``weight`` [w]; ``gate`` [b, s, heads * w] and ``bias`` [heads * w], the
    gate before its sigmoid. Returns ``rmsnorm(o, weight)`` over each head's
    ``w`` channels times ``sigmoid(gate + bias)``, [b, s, heads * w] in
    ``o``'s dtype; ``w`` whole lanes of 128. Differentiable in all four."""
    return _gate_fwd_call(
        o, weight.astype(F32)[None], gate, bias.astype(F32)[None], eps=eps,
        interpret=flash._needs_interpret())


def _gate_vjp_fwd(o, weight, gate, bias, eps):
    return norm_gate(o, weight, gate, bias, eps), (o, weight, gate, bias)


def _gate_vjp_bwd(eps, res, dy):
    o, weight, gate, bias = res
    do, dgate, dweight, dbias = _gate_bwd_call(
        o, weight.astype(F32)[None], gate, bias.astype(F32)[None], dy,
        eps=eps, interpret=flash._needs_interpret())
    return (do, dweight.reshape(-1, weight.shape[0]).sum(0).astype(weight.dtype),
            dgate, dbias.sum(0).astype(bias.dtype))


norm_gate.defvjp(_gate_vjp_fwd, _gate_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def decay(lin: jax.Array, bias: jax.Array, rate: jax.Array, w: int
          ) -> jax.Array:
    """``lin`` [b, s, heads * w], the decay gate's low-rank product; ``bias``
    and ``rate`` [heads * w] float32. Returns the log-decay a channel ``rate
    * softplus(lin + bias)``, heads first, [b, heads, s, w] float32 (``rate``
    is ``-exp(A_log)`` of the channel's head). Differentiable in all
    three."""
    return _decay_fwd_call(lin, bias[None], rate[None], w=w,
                           interpret=flash._needs_interpret())


def _decay_vjp_fwd(lin, bias, rate, w):
    return decay(lin, bias, rate, w), (lin, bias, rate)


def _decay_vjp_bwd(w, res, dg):
    lin, bias, rate = res
    dlin, dbias, drate = _decay_bwd_call(
        lin, bias[None], rate[None], dg, w=w,
        interpret=flash._needs_interpret())
    return dlin, dbias.sum(0), drate.sum(0)


decay.defvjp(_decay_vjp_fwd, _decay_vjp_bwd)
