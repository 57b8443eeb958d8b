"""The two decayed Gram matrices of a chunk of the chunked delta rule
(``ops/kda.py``), one Pallas call forward and one backward.

For a chunk of C tokens with queries and keys ``q``, ``k`` [C, d] and the
cumulative log-decay ``G`` [C, d] float32 (decreasing down the rows)::

    A_kk[r, i] = sum_c k_rc k_ic exp(G_rc - G_ic)     i <  r, else 0
    A_qk[r, i] = sum_c q_rc k_ic exp(G_rc - G_ic)     i <= r, else 0

``decayed_grams`` is what ``kda._decayed_gram`` computes twice, to the same
rule (``ops/kda.py``'s "Decays a channel"): every exponent is a difference
``G_r - G_i`` with ``r`` at or after ``i``, and ``exp(-G)`` is never formed.

Inside a ``sub``-row diagonal block a pair's decay is formed once, the mask
put in before the ``exp``, and used for both matrices forward, and once for
the four sums backward; all of it float32 on the VPU, eight rows (a vreg) at
a time against one column, a vreg of pairs wholly above the diagonal
skipped. What sets the forward's pace is the sum along the lanes (a pair's
sum over the channels: ~7 cycles of one of three XLUs a vreg, my reading of
the compiler's schedule, PR 50), so two vregs that the diagonal crosses, one
with ``a`` dead rows and one with ``8 - a``, share one such sum (``_packed``:
17 sums a block and matrix for its 136 pairs of rows, where a vreg a column
would take 24), and a row of ``G`` or ``k`` is spread over a vreg's sublanes
by the load that reads it. The backward's pace is set as much by spreading a
cotangent's column over the lanes, twice a vreg of pairs. Between
sub-blocks the products are ``x * exp(G - G_ref)`` against ``k * exp(G_ref -
G)``, ``G_ref`` the row block's first row, operands in the inputs' dtype and
float32 accumulation on the MXU, the reweighted operands formed in VMEM from
the tiles the call holds. A row block's ``[sub, C]`` results are written
once, whole.

The backward pulls both cotangents back in one call. With ``dx`` what a
matrix hands to its row operand and ``dk`` to its column operand, the
cotangent of ``G`` is ``x * dx - k * dk`` summed over the two matrices,
since a pair reads ``G`` through ``G_r - G_i`` alone (``G_ref`` cancels).

A call is named ``kda_grams_<fwd|bwd>_bh<b*h>_n<chunks>_c<C>_k<d>`` so that
a device trace shows it. On the CPU it runs interpreted
(``flash._needs_interpret``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash

F32 = jnp.float32
#: rows of a float32 vreg: what a diagonal block is worked in
_ROWS = 8
#: chunks a grid step holds at most (a segment of ``kda.SEGMENT`` whole)
_CHUNKS_A_STEP = 8

_dot, _NT, _NN = flash._dot, flash._NT, flash._NN
_TN = (((0,), (0,)), ((), ()))  # [n, m] x [n, d] -> [m, d]


def _pair_decay(Gh, gi, i: int, ro: int):
    """``exp(G_r - G_i)`` for the eight rows ``Gh`` that start at row ``ro``
    of a diagonal block against its row ``i`` (``gi`` [1, d]), 0 where ``r <
    i``; the mask goes in before the ``exp``."""
    d = Gh - gi
    if i > ro:
        r = jax.lax.broadcasted_iota(jnp.int32, d.shape, 0)
        d = jnp.where(r >= i - ro, d, -jnp.inf)
    return jnp.exp(d)


def _between(qr, kr, Gr, kf, G, before: int, cdt):
    """The operands of a row block's products against the ``before`` rows
    that precede it: (``[q; k] * exp(G - G_ref)`` [2 sub, d], ``k * exp(G_ref
    - G)`` [C, d] with zeros from ``before`` on, both in ``cdt``, and the two
    decays in float32)."""
    ref = Gr[0:1]
    e_r = jnp.exp(Gr - ref)
    X = jnp.concatenate([qr * e_r, kr * e_r], axis=0).astype(cdt)
    e_c = jnp.exp(ref - G[:before])
    kg = jnp.concatenate(
        [kf[:before] * e_c, jnp.zeros((G.shape[0] - before, G.shape[1]), F32)],
        axis=0).astype(cdt)
    return X, kg, e_r, e_c


def _packed(sub: int):
    """How a diagonal block's (vreg of eight rows, column) pairs go through
    the XLU, whose sums along the lanes are the kernel's slowest operation:
    [(ro, i, a, partner)], rows ``ro .. ro + 8`` against column ``i``, of
    which rows from ``a`` on are at or below the diagonal; ``partner`` is
    None or (ro', i') whose ``8 - a`` live rows ride in this one's dead
    ones, turned ``a`` sublanes, so that the two share one sum."""
    out, crossed = [], {}
    for ro in range(0, sub, _ROWS):
        for i in range(ro + _ROWS):
            a = max(i - ro, 0)
            if a:
                crossed.setdefault(a, []).append((ro, i))
            else:
                out.append((ro, i, 0, None))
    for a in sorted(crossed):
        if 2 * a < _ROWS:
            for mine, other in zip(crossed[a], crossed[_ROWS - a]):
                out.append((*mine, a, other))
        elif 2 * a == _ROWS:
            both = crossed[a]
            for mine, other in zip(both[0::2], both[1::2]):
                out.append((*mine, a, other))
            if len(both) % 2:
                out.append((*both[-1], a, None))
    return out


def _fwd_kernel(q_ref, k_ref, g_ref, akk_ref, aqk_ref, kf_s, *, chunks: int,
                sub: int):
    """``kf_s`` [C, d] float32: the chunk's keys, to read a row at a time
    spread over a vreg's sublanes (a load; of a value it is the XLU's)."""
    C = q_ref.shape[2]
    cdt = q_ref.dtype
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, C), 1)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, q_ref.shape[3]), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
    pairs = _packed(sub)

    def chunk(c, carry):
        qf, kf = q_ref[0, c].astype(F32), k_ref[0, c].astype(F32)
        G = g_ref[0, c]
        kf_s[...] = kf
        for I in range(C // sub):
            at = I * sub
            qr, kr, Gr = qf[at:at + sub], kf[at:at + sub], G[at:at + sub]

            def decayed_key(ro, i):  # k_i exp(G_r - G_i), rows ro .. ro + 8
                return kf_s[pl.ds(at + i, 1), :] * _pair_decay(
                    Gr[ro:ro + _ROWS], g_ref[0, c, pl.ds(at + i, 1), :], i, ro)

            Dq = {ro: jnp.zeros((_ROWS, C), F32)
                  for ro in range(0, sub, _ROWS)}
            Dk = dict(Dq)
            for ro, i, a, partner in pairs:
                kE = decayed_key(ro, i)
                pq, pk = qr[ro:ro + _ROWS] * kE, kr[ro:ro + _ROWS] * kE
                if partner is not None:
                    ro2, i2 = partner
                    kE2 = decayed_key(ro2, i2)
                    live = row8 >= a
                    pq = jnp.where(live, pq, pltpu.roll(
                        qr[ro2:ro2 + _ROWS] * kE2, a, 0))
                    pk = jnp.where(live, pk, pltpu.roll(
                        kr[ro2:ro2 + _ROWS] * kE2, a, 0))
                # spread over the lanes before a sublane is turned: [8, 1]
                # has no layout Mosaic turns without the XLU's permutes
                sq, sk = (jnp.broadcast_to(
                    jnp.sum(p, axis=-1, keepdims=True), (_ROWS, C))
                    for p in (pq, pk))
                # rows above the diagonal hold the partner's sums or zeros:
                # the masks below drop them
                Dq[ro] = jnp.where(lane == at + i, sq, Dq[ro])
                Dk[ro] = jnp.where(lane == at + i, sk, Dk[ro])
                if partner is not None:
                    back = _ROWS - a
                    Dq[ro2] = jnp.where(lane == at + i2,
                                        pltpu.roll(sq, back, 0), Dq[ro2])
                    Dk[ro2] = jnp.where(lane == at + i2,
                                        pltpu.roll(sk, back, 0), Dk[ro2])
            aqk = jnp.where(row + at >= col, jnp.concatenate(
                [Dq[ro] for ro in sorted(Dq)], axis=0), 0.0)
            akk = jnp.where(row + at > col, jnp.concatenate(
                [Dk[ro] for ro in sorted(Dk)], axis=0), 0.0)
            if I:
                X, kg, _, _ = _between(qr, kr, Gr, kf, G, at, cdt)
                off = _dot(X, kg, _NT)                          # [2 sub, C]
                aqk, akk = aqk + off[:sub], akk + off[sub:]
            aqk_ref[0, c, pl.ds(at, sub), :] = aqk
            akk_ref[0, c, pl.ds(at, sub), :] = akk
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


def _bwd_kernel(q_ref, k_ref, g_ref, dkk_ref, dqk_ref, dq_ref, dk_ref, dg_ref,
                kf_s, xq_s, xk_s, kk_s, *, chunks: int, sub: int):
    """``kf_s``: as the forward's. ``xq_s``, ``xk_s`` [C, d] float32: what
    ``A_qk`` hands to ``q`` and ``A_kk`` to its row operand; ``kk_s``: what
    both hand to their column operand."""
    C, d = q_ref.shape[2:]
    cdt = q_ref.dtype
    row = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (2 * sub, C), 1)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, d), 0)
    halves = range(0, sub, _ROWS)

    def chunk(c, carry):
        qf, kf = q_ref[0, c].astype(F32), k_ref[0, c].astype(F32)
        G = g_ref[0, c]
        kf_s[...] = kf
        for I in range(C // sub):
            at = I * sub
            qr, kr, Gr = qf[at:at + sub], kf[at:at + sub], G[at:at + sub]
            ctq = dqk_ref[0, c, pl.ds(at, sub), :]              # [sub, C]
            ctk = jnp.where(row + at > col,
                            dkk_ref[0, c, pl.ds(at, sub), :], 0.0)
            # a vreg of rows' two cotangents one over the other: a column of
            # both is spread over the lanes with one pattern
            both = [jnp.concatenate([ctq[ro:ro + _ROWS], ctk[ro:ro + _ROWS]],
                                    axis=0) for ro in halves]
            xq = [jnp.zeros((_ROWS, d), F32) for _ in halves]
            xk, kk = list(xq), list(xq)
            for i in range(sub):
                gi = g_ref[0, c, pl.ds(at + i, 1), :]
                ki = kf_s[pl.ds(at + i, 1), :]
                T = None
                for h, ro in enumerate(halves):
                    if i >= ro + _ROWS:
                        continue
                    rows = slice(ro, ro + _ROWS)
                    E = _pair_decay(Gr[rows], gi, i, ro)
                    cs = jnp.broadcast_to(
                        both[h][:, at + i:at + i + 1], (2 * _ROWS, d))
                    cq, ck = cs[:_ROWS], cs[_ROWS:]
                    kE = ki * E
                    xq[h] = xq[h] + cq * kE
                    xk[h] = xk[h] + ck * kE
                    t = (cq * qr[rows] + ck * kr[rows]) * E
                    T = t if T is None else T + t
                h = i // _ROWS
                kk[h] = jnp.where(row8 == i - h * _ROWS,
                                  jnp.sum(T, axis=0, keepdims=True), kk[h])
            xq, xk, kk = (jnp.concatenate(a, axis=0) for a in (xq, xk, kk))
            if I:
                X, kg, e_r, e_c = _between(qr, kr, Gr, kf, G, at, cdt)
                ct = jnp.where(col2 < at, jnp.concatenate([ctq, ctk], axis=0),
                               0.0).astype(cdt)                 # [2 sub, C]
                dX = _dot(ct, kg, _NN)                          # [2 sub, d]
                xq, xk = xq + dX[:sub] * e_r, xk + dX[sub:] * e_r
                kk_s[pl.ds(0, at), :] += _dot(ct, X, _TN)[:at] * e_c
            xq_s[pl.ds(at, sub), :] = xq
            xk_s[pl.ds(at, sub), :] = xk
            kk_s[pl.ds(at, sub), :] = kk
        dq, dxk, dkk = xq_s[...], xk_s[...], kk_s[...]
        dq_ref[0, c] = dq.astype(dq_ref.dtype)
        dk_ref[0, c] = (dxk + dkk).astype(dk_ref.dtype)
        dg_ref[0, c] = qf * dq + kf * (dxk - dkk)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


# ---------------------------------------------------------------- the calls

def _call(way: str, kernel, ins, outs, scratch: int, sub: int, interpret: bool):
    """One of the two calls: ``ins`` [b * h, n, C, ..] arrays, ``outs`` their
    results' shapes, ``scratch`` [C, d] float32 buffers; a grid step holds
    one (batch, head)'s chunks, the most that divide ``n`` up to
    ``_CHUNKS_A_STEP``."""
    bh, n, C, d = ins[0].shape
    chunks = max(c for c in range(1, _CHUNKS_A_STEP + 1) if n % c == 0)

    def spec(a):
        return pl.BlockSpec((1, chunks, *a.shape[2:]),
                            lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(kernel, chunks=chunks, sub=sub),
        grid=(bh, n // chunks),
        in_specs=[spec(a) for a in ins], out_specs=[spec(a) for a in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((C, d), F32)] * scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=f"kda_grams_{way}_bh{bh}_n{n}_c{C}_k{d}",
    )(*ins)


def _flat(a):
    """[b, h, n, ..] -> [b * h, n, ..]."""
    return a.reshape(-1, *a.shape[2:])


# Jitted, so that a step that holds the call several times (a layer's forward,
# its rebuilt segments, every ``kda`` layer) traces the kernel's unrolled
# body once a process and lowers it once a program: each trace and lowering
# is most of a second, which a compile cache does not save (12 of them were
# 8 s of the cell's ``setup_s``: my chip runs, PR 50). ``interpret`` is an
# argument, so what a test steered is part of the cache's key.

@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _fwd_call(q, k, G, *, sub: int, interpret: bool):
    tile = jax.ShapeDtypeStruct((*q.shape[:3], q.shape[2]), F32)
    return _call("fwd", _fwd_kernel, (q, k, G), [tile, tile], 1, sub,
                 interpret)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _bwd_call(q, k, G, dkk, dqk, *, sub: int, interpret: bool):
    outs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, G)]
    return _call("bwd", _bwd_kernel, (q, k, G, dkk, dqk), outs, 4, sub,
                 interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def decayed_grams(q: jax.Array, k: jax.Array, G: jax.Array, sub: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """q, k [b, h, n, C, d] (one dtype), G [b, h, n, C, d] float32 ->
    (``A_kk``, ``A_qk``) [b, h, n, C, C] float32 (module docstring); ``sub``
    rows a sub-block, a multiple of 8 (16 for operands of 16 bits) that
    divides C, and d a multiple of 128. Differentiable in ``q``, ``k`` and
    ``G``."""
    lead = q.shape[:3]
    akk, aqk = _fwd_call(_flat(q), _flat(k), _flat(G), sub=sub,
                         interpret=flash._needs_interpret())
    return (akk.reshape(*lead, *akk.shape[2:]),
            aqk.reshape(*lead, *aqk.shape[2:]))


def _grams_fwd(q, k, G, sub):
    return decayed_grams(q, k, G, sub), (q, k, G)


def _grams_bwd(sub, res, cts):
    q, k, G = res
    dkk, dqk = (_flat(ct.astype(F32)) for ct in cts)
    dq, dk, dG = _bwd_call(_flat(q), _flat(k), _flat(G), dkk, dqk, sub=sub,
                           interpret=flash._needs_interpret())
    return dq.reshape(q.shape), dk.reshape(k.shape), dG.reshape(G.shape)


decayed_grams.defvjp(_grams_fwd, _grams_bwd)
