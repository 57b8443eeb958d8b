"""Everything of a chunk of the chunked delta rule (``ops/kda.py``) that does
not read the state, one Pallas call forward and one backward.

For a chunk of C tokens with ``q``, ``k`` [C, dk] and ``v`` [C, dv] in the
inputs' dtype, the log-decay ``g`` [C, dk] float32 and the step ``beta`` [C]
float32, ``insides`` is what ``kda._insides`` computes in XLA's form::

    G    = the running sum of g down the rows            (never leaves VMEM)
    A_kk[r, i] = sum_c k_rc k_ic exp(G_rc - G_ic)        i <  r, else 0
    A_qk[r, i] = sum_c q_rc k_ic exp(G_rc - G_ic)        i <= r, else 0
    A    = Diag(beta) A_kk           X = (I + A)^-1      T = X Diag(beta)
    W    = T (K * exp(G))            U = T V (float32)
    Qg   = Q * exp(G)                Kend = K * exp(G_C - G)   gend = exp(G_C)

and hands back ``W``, ``U``, ``A_qk``, ``Qg``, ``Kend`` and ``gend`` with
``_insides``' shapes and dtypes. A grid step holds one (batch, head)'s
chunks.

The running sum is a product on the MXU at float32's own precision: the
triangle of ones is exact in bfloat16 and ``g`` goes in as three bfloat16
pieces that add up to it, accumulated in float32 (``_running_sum``).

The two decayed products, to ``ops/kda.py``'s rule ("Decays a channel"):
every exponent is a difference ``G_r - G_i`` with ``r`` at or after ``i``, and
``exp(-G)`` is never formed. Inside a ``sub``-row diagonal block a pair's
decay is formed once, the mask put in before the ``exp``, and used for both
matrices forward and once for the four sums backward; all of it float32 on
the VPU, eight rows (a vreg) at a time against one column, a vreg of pairs
wholly above the diagonal skipped. What sets the forward's pace is the sum
along the lanes (a pair's sum over the channels: ~7 cycles of one of three
XLUs a vreg, the compiler's schedule, PR 50), so two vregs that the diagonal
crosses, one with ``a`` dead rows and one with ``8 - a``, share one such sum
(``_packed``: 17 sums a block and matrix for its 136 pairs of rows, where a
vreg a column would take 24), and a row of ``G`` or ``k`` is spread over a
vreg's sublanes by the load that reads it. The backward's pace is set as much
by spreading a cotangent's column over the lanes, twice a vreg of pairs.
Between sub-blocks the products are ``x * exp(G - G_ref)`` against ``k *
exp(G_ref - G)``, ``G_ref`` the row block's first row, operands in the inputs'
dtype and float32 accumulation on the MXU (``_between``). ``A``'s row operand
is ``beta k``, so that the sums are ``A``'s own and ``A_kk`` is never formed
(the XLA form multiplies by beta after the products; in bfloat16 the two
round at different places).

The inverse, block by block as ``ops/kda.py``'s "Precision" asks. A pair's
sum along the lanes leaves a column of ``A``'s diagonal block spread over the
lanes, which is the operand a forward substitution by columns wants: a
16-row diagonal block's inverse is 23 multiply-and-subtracts of a column
(spread over the lanes) and a finished row (spread over the sublanes), float32
on the VPU, exact where the XLA form's Neumann product carries its binomial
growth, and with no sum along the lanes of its own (``_block_inverse``). The
blocks are merged by block forward substitution, float32 operands at
``highest`` on the MXU (``_inverse_rows``). ``T`` is cast to the products'
dtype, and ``W`` and ``U`` are two products with float32 accumulation.

The backward reads the inputs and one thing the forward formed, the inverse
``X`` (float32, a segment's 4 MiB, written side by side for two chunks so that
a row is whole lanes): rebuilding it would be the forward's sums and merge
again, 4 to 5 ms a layer and step on the chip, where reading it is nothing.
From it: ``dT = dW (K e^G)^T + dU V^T``, the inverse's own pull-back ``dA =
-X^T dX X^T`` below the diagonal (``highest``), the backward sums of both
products in one walk (``dA`` with the row operand ``beta k``, and the
cotangent of ``A_qk``: with ``dx`` what a matrix hands to its row operand and
``dk`` to its column operand, the cotangent of ``G`` is ``x * dx - k * dk``
summed over the two, since a pair reads ``G`` through ``G_r - G_i`` alone and
``G_ref`` cancels), beta's cotangent from both, the elementwise terms through
``e^G``, ``e^(G_C - G)`` and ``gend``, and the reverse running sum that turns
the cotangent of ``G`` into ``g``'s.

What sets the pace, and the order things are written in because of it
(``_fwd_kernel``, ``_bwd_kernel``; the compiler's schedule for a described
v5e, PERF.md section 6, PR 53): a product at ``highest`` is six passes of the
MXU over operands the VPU has split in three, and the MXU takes its products
in the order they are written, so a chain of substitution steps holds every
product behind it; and the compiler lays such a chain beside another chunk's
sums along the lanes (the XLU's) only where something after those sums waits
for it. So the kernels work several chunks a trip of their loop, write the
part of the next chunk that the MXU does between the sub-blocks of this
chunk's sums, and write chains that nothing waits for a step of every
chunk's at a time. ``tests/test_aot_tpu_compile.py`` holds the bundles a chunk
that this order reaches.

A call is named ``kda_insides_<fwd|bwd>_bh<b*h>_n<chunks>_c<C>_k<dk>_v<dv>`` so
that a device trace shows it. On the CPU it runs interpreted
(``flash._needs_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash

F32 = jnp.float32
BF16 = jnp.bfloat16
_HIGHEST, _DEFAULT = jax.lax.Precision.HIGHEST, jax.lax.Precision.DEFAULT
#: rows of a float32 vreg: what a diagonal block is worked in
_ROWS = 8
#: chunks a grid step holds at most (a segment of ``kda.SEGMENT`` whole)
_CHUNKS_A_STEP = 8
#: chunks a trip of the forward's loop works at most, and of the backward's
#: (``_trips``). On the chip, a call alone over a layer's 8,192 chunks (my
#: runs, PR 53): the forward 5.93-6.06 ms at 2 and 5.74 at 4; the backward 9.67 at
#: 2, 8.63 at 4 and 8.47 at 8. A trip's chunks are unrolled, and a process
#: pays for that before its first step: tracing and lowering a layer's
#: recurrence took 4.0 s at 2 and 2, 8.4 s at 4 and 4 (the XLA form with
#: the two products alone as a kernel, PR 50's: 3.1 s), which the cell's ``setup_s`` showed second for
#: second. So 4 where it returns a millisecond a layer and 2 where a fifth
_TOGETHER = 2
_TOGETHER_BACK = 4


_dot, _NT, _NN = flash._dot, flash._NT, flash._NN
_TN = (((0,), (0,)), ((), ()))  # [n, m] x [n, d] -> [m, d]


def _dot_f32(a, b, dims):
    """A product of float32 operands at float32's precision."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=F32)


def _iotas(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _running_sum(g, reverse: bool = False):
    """g [C, d] float32 -> its running sum down the rows (``reverse``: up
    them), at float32's precision on the MXU: the triangle of ones is exact
    in bfloat16, ``g`` is three bfloat16 pieces that add up to it, and the
    three products accumulate in float32."""
    row, col = _iotas((g.shape[0],) * 2)
    tri = jnp.where(row <= col if reverse else row >= col, 1.0, 0.0).astype(BF16)
    out, rest = None, g
    for _ in range(3):
        piece = rest.astype(BF16)
        rest = rest - piece.astype(F32)
        # (exact as it stands: whatever precision the caller's context asks
        # of a product, these operands are bfloat16's own)
        part = jax.lax.dot_general(tri, piece, _NN, precision=_DEFAULT,
                                   preferred_element_type=F32)
        out = part if out is None else out + part
    return out


def _down_the_rows(row_vector, C: int):
    """[1, C] along the lanes -> [C, 1] down the sublanes (one sum along the
    lanes a vreg of rows, of a single live lane each: exact)."""
    row, col = _iotas((C, C))
    return jnp.sum(jnp.where(row == col, row_vector, 0.0), axis=1,
                   keepdims=True)


def _along_the_lanes(column, C: int):
    """``_down_the_rows``' inverse: [C, 1] -> [1, C]."""
    row, col = _iotas((C, C))
    return jnp.sum(jnp.where(row == col, column, 0.0), axis=0, keepdims=True)


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


def _diagonal(qr, kr, decayed_key, pairs, at: int, C: int):
    """A diagonal block's sums, pair by pair (``_packed``), of the row
    operands ``qr`` and ``kr`` [sub, d] float32: the queries' as a
    ``[sub, C]`` tile with column ``i`` of the block at lane ``at + i`` (what
    lies above the diagonal is the caller's to mask), the keys' as the sums
    left them, {(ro, i): [8, C], rows ``ro .. ro + 8`` of column ``i`` spread
    over the lanes} (rows not below ``i`` hold anything)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, C), 1)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, kr.shape[1]), 0)
    tile = {ro: jnp.zeros((_ROWS, C), F32) for ro in range(0, kr.shape[0], _ROWS)}
    cols = {}
    for ro, i, a, partner in pairs:
        kE = decayed_key(ro, i)
        pq, pk = qr[ro:ro + _ROWS] * kE, kr[ro:ro + _ROWS] * kE
        if partner is not None:
            ro2, i2 = partner
            kE2 = decayed_key(ro2, i2)
            live = row8 >= a
            pq = jnp.where(live, pq, pltpu.roll(qr[ro2:ro2 + _ROWS] * kE2, a, 0))
            pk = jnp.where(live, pk, pltpu.roll(kr[ro2:ro2 + _ROWS] * kE2, a, 0))
        # spread over the lanes before a sublane is turned: [8, 1] has no
        # layout Mosaic turns without the XLU's permutes
        sq, sk = (jnp.broadcast_to(jnp.sum(p, axis=-1, keepdims=True), (_ROWS, C))
                  for p in (pq, pk))
        tile[ro] = jnp.where(lane == at + i, sq, tile[ro])
        cols[ro, i] = sk
        if partner is not None:
            back = _ROWS - a
            tile[ro2] = jnp.where(lane == at + i2, pltpu.roll(sq, back, 0),
                                  tile[ro2])
            cols[ro2, i2] = pltpu.roll(sk, back, 0)
    return jnp.concatenate([tile[ro] for ro in sorted(tile)], axis=0), cols


def _block_inverse(cols, sub: int, C: int):
    """``(I + D)^-1`` of a diagonal block whose strictly lower columns are
    ``cols`` (``_diagonal``'s): forward substitution a column at a time, a
    finished row ``j`` taken off every row below it. Returns [sub, C], the
    block in lanes ``0 .. sub``."""
    lane, row8 = (jax.lax.broadcasted_iota(jnp.int32, (_ROWS, C), d)
                  for d in (1, 0))
    halves = range(0, sub, _ROWS)
    X = {ro: jnp.where(lane == row8 + ro, 1.0, 0.0) for ro in halves}
    for j in range(sub - 1):
        at = j // _ROWS * _ROWS
        row_j = jnp.broadcast_to(X[at][j - at:j - at + 1], (_ROWS, C))
        for ro in halves:
            if ro + _ROWS - 1 <= j:
                continue        # no row of this vreg lies below j
            c = cols[ro, j]
            if j >= ro:         # some of its rows do not: they stay
                c = jnp.where(row8 + ro > j, c, 0.0)
            X[ro] = X[ro] - c * row_j
    return jnp.concatenate([X[ro] for ro in halves], axis=0)


def _inverse_rows(rows, A_r, X_loc, at: int, sub: int, C: int):
    """Block forward substitution: the row block at ``at`` of ``(I + A)^-1``
    from the row blocks before it (``rows``, [sub, C] each), the block's rows
    of ``A`` (``A_r`` [sub, C]) and its diagonal block's inverse (``X_loc``,
    ``_block_inverse``'s)."""
    if not at:
        return X_loc
    row, col = _iotas((sub, C))
    eye = jnp.where(col == row + at, 1.0, 0.0)
    R = eye - _dot_f32(A_r[:, :at], jnp.concatenate(rows, axis=0), _NN)
    return _dot_f32(X_loc[:, :sub], R, _NN)


def _offs(qf, kb, kf, G, sub: int, cdt):
    """A chunk's products between sub-blocks (``_between``'s operands): for each row block but the first its rows of ``A_qk`` over
    its rows of ``A`` against the rows before it, [2 sub, C], zeros from the
    block's own columns on."""
    out = []
    for at in range(sub, kf.shape[0], sub):
        X, kg, _, _ = _between(qf[at:at + sub], kb[at:at + sub],
                               G[at:at + sub], kf, G, at, cdt)
        out.append(_dot(X, kg, _NT))
    return out


def _blocks(qf, kb, G, k_row, G_row, pairs, sub: int):
    """The diagonal blocks of a chunk, first to last, from its queries, its
    keys times their steps (``kb = beta k``, the row operand of ``A =
    Diag(beta) A_kk``) and its running sums, [C, dk] float32; ``k_row(i)``
    and ``G_row(i)`` read row ``i`` of the keys and of ``G`` spread over a
    vreg's sublanes (a load). Yields (the block's rows of ``A_qk`` [sub, C]
    on and below the diagonal, the block's inverse as ``_block_inverse``
    forms it)."""
    C = kb.shape[0]
    row, col = _iotas((sub, C))
    for at in range(0, C, sub):
        Gr = G[at:at + sub]

        def decayed_key(ro, i):  # k_i exp(G_r - G_i), rows ro .. ro + 8
            return k_row(at + i) * _pair_decay(
                Gr[ro:ro + _ROWS], G_row(at + i), i, ro)

        tile, cols = _diagonal(qf[at:at + sub], kb[at:at + sub], decayed_key,
                               pairs, at, C)
        yield (jnp.where(row + at >= col, tile, 0.0),
               _block_inverse(cols, sub, C))


def _side(chunks: int) -> int:
    """Chunks whose inverses lie side by side along the lanes of one
    ``[C, side * C]`` tile of ``x_ref``: a row of 64 float32 alone is half a
    lane tile, and half-empty DMAs."""
    return 2 - chunks % 2


def _together(chunks: int, most: int) -> int:
    """Chunks a trip of a kernel's loop works, ``most`` at most: whole tiles
    of ``x_ref``."""
    side = _side(chunks)
    return side * max(n for n in range(1, max(most // side, 1) + 1)
                      if chunks // side % n == 0)


def _steps_chunks(n: int) -> int:
    """Chunks a grid step holds: the most that divide ``n`` up to
    ``_CHUNKS_A_STEP``."""
    return max(c for c in range(1, _CHUNKS_A_STEP + 1) if n % c == 0)


def _drain(stages):
    for _ in stages:
        pass


def _trips(chunks: int, together: int, ahead, work, after=None):
    """The loop both kernels run over a grid step's chunks, ``together`` a
    trip: ``work(t, u, between)`` for each chunk ``u`` of trip ``t``, where
    ``between`` is the next chunk's ``ahead(t, u + 1)``, a generator of
    stages for ``work`` to advance between its own parts (the first chunk's
    runs whole before any work); then ``after(t)``."""
    def trip(t, carry):
        _drain(ahead(t, 0))
        for u in range(together):
            work(t, u, ahead(t, u + 1) if u + 1 < together else iter(()))
        if after is not None:
            after(t)
        return carry

    jax.lax.fori_loop(0, chunks // together, trip, 0)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_ref, u_ref, aqk_ref,
                qg_ref, kend_ref, gend_ref, x_ref, *scratch, chunks: int,
                sub: int):
    """A trip of the loop works ``_TOGETHER`` chunks. A chunk's forward is
    three parts: what waits for nothing but its ``g`` (``first``: the running
    sum, the products between sub-blocks, the reweighted operands; the
    MXU's), the sums along the lanes and the diagonal blocks' inverses
    (``sums``: the XLU's and the VPU's pace, no product), and the merge,
    ``W`` and ``U`` (``merged``: a chain of products, each waiting for the
    one before). The MXU takes its products in the order they are written,
    and the compiler lays products beside the sums only if something after
    the sums waits for them. So a trip writes the next chunk's ``first`` a
    stage at a time between the sub-blocks of this chunk's ``sums``, and the
    chains of all its chunks at its end, a step of every chain before the
    next step of any, so that one fills another's waits.

    Scratch, a buffer a chunk of the trip: ``kf_s`` [C, dk] float32 the keys
    and ``G_s`` the running sums, to read a row at a time spread over a
    vreg's sublanes (a load); ``kb_s`` ``beta k``; ``kg_s`` ``K e^G`` in the
    products' dtype; ``off_s`` [blocks - 1, 2 sub, C] ``_offs``' products;
    ``xl_s`` [C, C] the diagonal blocks' inverses."""
    C = q_ref.shape[2]
    cdt = q_ref.dtype
    pairs = _packed(sub)
    side = _side(chunks)
    together = _together(chunks, _TOGETHER)
    kf_s, G_s, kb_s, kg_s, off_s, xl_s = (
        scratch[i * together:(i + 1) * together] for i in range(6))

    def first(t, u):
        """Chunk ``t * together + u``, a stage a ``next``."""
        c = t * together + u
        kf = k_ref[0, c].astype(F32)
        G = _running_sum(g_ref[0, c])
        kf_s[u][...], G_s[u][...] = kf, G
        yield
        qf = q_ref[0, c].astype(F32)
        kb = _down_the_rows(beta_ref[0, c], C) * kf
        kb_s[u][...] = kb
        for I, off in enumerate(_offs(qf, kb, kf, G, sub, cdt)):
            off_s[u][I] = off
        yield
        eG, Gend = jnp.exp(G), G[-1:]
        kg_s[u][...] = (kf * eG).astype(cdt)
        qg_ref[0, c] = (qf * eG).astype(cdt)
        kend_ref[0, c] = (kf * jnp.exp(Gend - G)).astype(cdt)
        gend_ref[0, c] = jnp.exp(Gend)
        yield

    def sums(t, u, between):
        """The same chunk's ``A_qk`` and diagonal blocks' inverses; a stage
        of ``between`` (the next chunk's ``first``) after each sub-block."""
        c = t * together + u
        for I, (aqk, X_b) in enumerate(_blocks(
                q_ref[0, c].astype(F32), kb_s[u][...], G_s[u][...],
                lambda i: kf_s[u][pl.ds(i, 1), :],
                lambda i: G_s[u][pl.ds(i, 1), :], pairs, sub)):
            if I:
                aqk = aqk + off_s[u][I - 1, pl.ds(0, sub), :]
            aqk_ref[0, c, pl.ds(I * sub, sub), :] = aqk.astype(cdt)
            xl_s[u][pl.ds(I * sub, sub), :] = X_b
            next(between, None)
        _drain(between)

    def merged(t):
        ins = [(off_s[u][...], xl_s[u][...]) for u in range(together)]
        rows = [[] for _ in ins]
        for I in range(C // sub):
            for mine, (A, X_loc) in zip(rows, ins):
                mine.append(_inverse_rows(
                    mine, A[I - 1, sub:] if I else None,
                    X_loc[I * sub:(I + 1) * sub], I * sub, sub, C))
        Xs = [jnp.concatenate(mine, axis=0) for mine in rows]
        for j in range(together // side):
            x_ref[0, t * (together // side) + j] = jnp.concatenate(
                Xs[j * side:(j + 1) * side], axis=1)
        Ts = [(X * beta_ref[0, t * together + u]).astype(cdt)
              for u, X in enumerate(Xs)]
        for u, T in enumerate(Ts):
            w_ref[0, t * together + u] = _dot(T, kg_s[u][...], _NN).astype(cdt)
        for u, T in enumerate(Ts):
            c = t * together + u
            u_ref[0, c] = _dot(T, v_ref[0, c], _NN)

    _trips(chunks, together, first, sums, merged)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, x_ref, dw_ref, du_ref,
                dqk_ref, dqg_ref, dkend_ref, dgend_ref, dq_ref, dk_ref, dv_ref,
                dg_ref, dbeta_ref, *scratch, chunks: int, sub: int):
    """A trip of the loop works ``_TOGETHER_BACK`` chunks. A chunk's backward
    is two halves: from the inverse ``x_ref`` the forward left, the
    cotangents of ``T``, ``v``, ``K e^G`` and ``A`` (``back``: a chain of
    products, the MXU's pace), then the backward sums of the two decayed
    products (one walk for both, ``A``'s row operand ``beta k``), the
    reweighted operands' terms and the reverse running sum (``pulled``: the
    XLU's and the VPU's pace). The first waits for nothing of the chunk
    before, so a trip writes the next chunk's ``back`` a stage at a time
    between the sub-blocks of this chunk's ``pulled``: the MXU takes its
    products in the order they are written, and the compiler lays a chain
    beside the sums only if something after the sums waits for it.

    Scratch, a buffer a chunk of the trip: ``kf_s`` [C, dk] float32 the keys
    and ``G_s`` the running sums, as the forward's; ``dkg_s`` [C, dk] and
    ``dkk_s`` [C, C] the cotangents of ``K e^G`` and of ``A``; and, one for
    all, ``xq_s``, ``xk_s``, ``kk_s`` [C, dk]: what ``A_qk`` hands to ``q``
    and ``A`` to its row operand; what both hand to their column operand."""
    C, d = q_ref.shape[2:]
    cdt = q_ref.dtype
    side = _side(chunks)
    together = _together(chunks, _TOGETHER_BACK)
    kf_s, G_s, dkg_s, dkk_s = (scratch[i * together:(i + 1) * together]
                               for i in range(4))
    xq_s, xk_s, kk_s = scratch[4 * together:]
    rowC, colC = _iotas((C, C))
    col2 = jax.lax.broadcasted_iota(jnp.int32, (2 * sub, C), 1)
    row8 = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, d), 0)
    last = jax.lax.broadcasted_iota(jnp.int32, (C, d), 0) == C - 1
    halves = range(0, sub, _ROWS)

    def back(t, u):
        """Chunk ``t * together + u`` back to the cotangents that need the
        inverse, a stage a ``next``."""
        c = t * together + u
        kf = k_ref[0, c].astype(F32)
        G = _running_sum(g_ref[0, c])
        kf_s[u][...], G_s[u][...] = kf, G
        yield
        # (the forward's trips' inverses lie side by side in a tile of
        # ``x_ref``, and a trip here is whole trips of the forward's)
        at = u % side * C
        X = x_ref[0, t * (together // side) + u // side][:, at:at + C]
        beta_row = beta_ref[0, c]
        # W = T (K e^G) and U = T V back to T, the reweighted keys and v
        T = (X * beta_row).astype(cdt)
        dw, du = dw_ref[0, c], du_ref[0, c].astype(cdt)
        dT = (_dot(dw, (kf * jnp.exp(G)).astype(cdt), _NT)
              + _dot(du, v_ref[0, c], _NT))                     # [C, C]
        yield
        dkg_s[u][...] = _dot(T, dw, _TN)
        dv_ref[0, c] = _dot(T, du, _TN).astype(dv_ref.dtype)
        yield
        # T = X Diag(beta), X = (I + A)^-1: dA = -X^T dX X^T below the diagonal
        Y = _dot_f32(X, dT * beta_row, _TN)
        yield
        dkk_s[u][...] = jnp.where(rowC > colC, -_dot_f32(Y, X, _NT), 0.0)
        dbeta_ref[0, c] = jnp.sum(X * dT, axis=0, keepdims=True)
        yield

    def pulled(t, u, between):
        """The same chunk's two decayed products back to ``q``, ``k``,
        ``beta`` and ``G``, the reweighted operands' terms and ``G``'s
        cotangent to ``g``'s; a stage of ``between`` (the next chunk's
        ``back``) after each sub-block."""
        c = t * together + u
        qf, kf, G = q_ref[0, c].astype(F32), kf_s[u][...], G_s[u][...]
        beta_col = _down_the_rows(beta_ref[0, c], C)
        kb = beta_col * kf
        for at in range(0, C, sub):
            qr, kr, Gr = qf[at:at + sub], kb[at:at + sub], G[at:at + sub]
            ctq = dqk_ref[0, c, pl.ds(at, sub), :].astype(F32)  # [sub, C]
            ctk = dkk_s[u][pl.ds(at, sub), :]
            # a vreg of rows' two cotangents one over the other: a column of
            # both is spread over the lanes with one pattern
            both = [jnp.concatenate([ctq[ro:ro + _ROWS], ctk[ro:ro + _ROWS]],
                                    axis=0) for ro in halves]
            xq = [jnp.zeros((_ROWS, d), F32) for _ in halves]
            xk, kk = list(xq), list(xq)
            for i in range(sub):
                gi = G_s[u][pl.ds(at + i, 1), :]
                ki = kf_s[u][pl.ds(at + i, 1), :]
                acc = None
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
                    term = (cq * qr[rows] + ck * kr[rows]) * E
                    acc = term if acc is None else acc + term
                h = i // _ROWS
                kk[h] = jnp.where(row8 == i - h * _ROWS,
                                  jnp.sum(acc, axis=0, keepdims=True), kk[h])
            xq, xk, kk = (jnp.concatenate(a, axis=0) for a in (xq, xk, kk))
            if at:
                Xr, kgr, e_r, e_c = _between(qr, kr, Gr, kf, G, at, cdt)
                ct = jnp.where(col2 < at, jnp.concatenate([ctq, ctk], axis=0),
                               0.0).astype(cdt)                 # [2 sub, C]
                dX = _dot(ct, kgr, _NN)                         # [2 sub, d]
                xq, xk = xq + dX[:sub] * e_r, xk + dX[sub:] * e_r
                kk_s[pl.ds(0, at), :] += _dot(ct, Xr, _TN)[:at] * e_c
            xq_s[pl.ds(at, sub), :] = xq
            xk_s[pl.ds(at, sub), :] = xk
            kk_s[pl.ds(at, sub), :] = kk
            next(between, None)
        _drain(between)
        dq, dkb, dkk = xq_s[...], xk_s[...], kk_s[...]
        dbeta_ref[0, c] += _along_the_lanes(
            jnp.sum(dkb * kf, axis=1, keepdims=True), C)
        # Q e^G, K e^G, K e^(G_C - G) and e^(G_C)
        eG, eE = jnp.exp(G), jnp.exp(G[-1:] - G)
        dqg, dkend = dqg_ref[0, c].astype(F32), dkend_ref[0, c].astype(F32)
        dkg = dkg_s[u][...]
        dq_ref[0, c] = (dq + dqg * eG).astype(dq_ref.dtype)
        dk_ref[0, c] = (beta_col * dkb + dkk + dkg * eG
                        + dkend * eE).astype(dk_ref.dtype)
        to_end = dkend * kf * eE
        dG = (qf * dq + kb * dkb - kf * dkk + (dqg * qf + dkg * kf) * eG
              - to_end)
        at_end = (jnp.sum(to_end, axis=0, keepdims=True)
                  + dgend_ref[0, c] * jnp.exp(G[-1:]))
        dg_ref[0, c] = _running_sum(jnp.where(last, dG + at_end, dG),
                                    reverse=True)

    _trips(chunks, together, back, pulled)


# ---------------------------------------------------------------- the calls

def _call(way: str, kernel, ins, outs, scratch, sub: int, interpret: bool):
    """One of the two calls: ``ins`` [b * h, n, ..] arrays of two trailing
    dimensions, ``outs`` their results' shapes, ``scratch(chunks)`` the
    buffers' (shape, dtype); a grid step holds one (batch, head)'s chunks,
    the most that divide ``n`` up to ``_CHUNKS_A_STEP``."""
    bh, n, C, dk = ins[0].shape
    dv = ins[2].shape[-1]
    chunks = _steps_chunks(n)

    def spec(a):  # (the inverses come ``_side`` chunks a row)
        return pl.BlockSpec((1, a.shape[1] * chunks // n, *a.shape[2:]),
                            lambda i, j: (i, j, 0, 0), memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(kernel, chunks=chunks, sub=sub),
        grid=(bh, n // chunks),
        in_specs=[spec(a) for a in ins], out_specs=[spec(a) for a in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM(*s) for s in scratch(chunks)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=f"kda_insides_{way}_bh{bh}_n{n}_c{C}_k{dk}_v{dv}",
    )(*ins)


def _flat(a):
    """[b, h, n, ..] -> [b * h, n, ..]."""
    return a.reshape(-1, *a.shape[2:])


def _inverses(q):
    """The shape the forward leaves the chunks' inverses in for the backward:
    [b * h, n / side, C, side * C] float32 (``_side``)."""
    bh, n, C, _ = q.shape
    side = _side(_steps_chunks(n))
    return jax.ShapeDtypeStruct((bh, n // side, C, side * C), F32)


def _like(a, dtype=None, last=None):
    return jax.ShapeDtypeStruct(
        a.shape if last is None else (*a.shape[:-1], last), dtype or a.dtype)


# Jitted, so that a step that holds the calls several times (a layer's
# forward, its rebuilt segments, every ``kda`` layer) traces each kernel's
# unrolled body once a process and lowers it once a program: each trace and
# lowering is seconds, which a compile cache does not save (12 of PR 50's were
# 8 s of the cell's ``setup_s``). ``interpret`` is an argument, so what a test
# steered is part of the cache's key.

@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _fwd_call(q, k, v, g, beta, *, sub: int, interpret: bool):
    C, dk = q.shape[2:]
    outs = [_like(q), _like(v, F32), _like(q, last=C), _like(q), _like(q),
            jax.ShapeDtypeStruct((*q.shape[:2], 1, dk), F32),
            _inverses(q)]

    def scratch(n):  # a buffer a chunk of a trip: the compiler tells them apart
        n = _together(n, _TOGETHER)
        return ([((C, dk), F32)] * 3 * n + [((C, dk), q.dtype)] * n
                + [((C // sub - 1, 2 * sub, C), F32)] * n + [((C, C), F32)] * n)

    return _call("fwd", _fwd_kernel, (q, k, v, g, beta), outs, scratch, sub,
                 interpret)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _bwd_call(q, k, v, g, beta, X, *cts, sub: int, interpret: bool):
    C, dk = q.shape[2:]
    outs = [_like(a) for a in (q, k, v, g, beta)]

    def scratch(n):  # a buffer a chunk of a trip: the compiler tells them apart
        n = _together(n, _TOGETHER_BACK)
        return ([((C, dk), F32)] * 3 * n + [((C, C), F32)] * n
                + [((C, dk), F32)] * 3)

    return _call("bwd", _bwd_kernel, (q, k, v, g, beta, X, *cts), outs,
                 scratch, sub, interpret)


def _with_inverse(q, k, v, g, beta, sub):
    """``insides``' six results and the chunks' inverses as the backward
    reads them (``_inverses``)."""
    lead = q.shape[:3]
    *outs, gend, X = _fwd_call(
        *map(_flat, (q, k, v, g, beta[..., None, :])), sub=sub,
        interpret=flash._needs_interpret())
    whole = lambda a: a.reshape(*lead, *a.shape[2:])
    return (*map(whole, outs), gend.reshape(*lead, -1)), X


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def insides(q, k, v, g, beta, sub: int):
    """``kda._insides`` as the kernel: q, k [b, h, n, C, dk] and v [b, h, n,
    C, dv] of one dtype, g [b, h, n, C, dk] and beta [b, h, n, C] float32 ->
    (W [.., C, dk], U [.., C, dv] float32, A_qk [.., C, C], Qg, Kend
    [.., C, dk], gend [b, h, n, dk] float32). ``sub`` rows a sub-block, 16;
    ``dk`` and ``dv`` multiples of 128. Differentiable in all five."""
    return _with_inverse(q, k, v, g, beta, sub)[0]


def _insides_fwd(q, k, v, g, beta, sub):
    outs, X = _with_inverse(q, k, v, g, beta, sub)
    return outs, (q, k, v, g, beta, X)


def _insides_bwd(sub, res, cts):
    *inputs, X = res
    dW, dU, dAqk, dQg, dKend, dgend = cts
    beta = inputs[-1]
    grads = _bwd_call(
        *map(_flat, (*inputs[:-1], beta[..., None, :])), X,
        *map(_flat, (dW, dU, dAqk, dQg, dKend, dgend[..., None, :])),
        sub=sub, interpret=flash._needs_interpret())
    return tuple(a.reshape(b.shape) for a, b in zip(grads, inputs))


insides.defvjp(_insides_fwd, _insides_bwd)
