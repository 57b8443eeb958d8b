"""The hyper-connections' four passes over the widened stream
(``ops/hyper.py``, ``impl="pallas"``), each a Pallas call that holds a tile
of tokens' ``n`` whole rows in VMEM and reads them once::

    mix_in  forward   rows -> h, the coefficients
    mix_out forward   rows, branch, coefficients -> rows
    mix_out backward  rows' cotangent, rows, branch, coefficients ->
                      branch's, rows' (through H_res), coefficients'
    mix_in  backward  h's, rows' so far, coefficients' cotangents, rows ->
                      rows', and a tile's share of the leaves'

A grid step is ``tile`` tokens. What is small lives with the tokens on the
lanes, float32: ``phi``'s product comes off the MXU as ``[n^2 + 2n, tile]``
(``d`` contracted of both operands, bf16 operands and a float32 sum, ``g``
folded into ``phi`` outside), the sum of squares is folded to 128 lanes on
the VPU and turned once, and ``rsqrt``, the sigmoids, ``exp``, the clamp and
Sinkhorn's iterations run on ``n`` slabs ``[n, tile]`` (a column's sum is a
sum of slabs, a row's a sum along the sublanes), never through HBM. What
multiplies a row lives with the tokens on the sublanes: the coefficients
leave ``mix_in`` as ONE array ``[tokens, 128]`` float32, a coefficient a
lane (``pre | post | res``, row by row; 512 B a token beside the rows'
28 KB), turned once a tile, so that a row's ``[sub, d]`` slab takes its
coefficient as a ``[sub, 1]`` column and ``mix_out``'s two calls turn
nothing; their cotangent comes back in the same array. The passes over the
rows walk the tile ``_SUB`` tokens a trip, a row float32 from its load to
its store; the sums over ``d`` of the backward (``dH_post``, ``dH_res``,
``dH_pre``: n^2 + 2n a token) are folded to 128 lanes on the VPU, one sum
along the lanes a coefficient and eight tokens.

``mix_in`` hands the rows on as its third result, which ``mix_out`` reads in
their place: their cotangent so far then enters ``mix_in``'s backward call,
which adds its own and writes the rows' once (no pass of XLA's to sum two
cotangents of one stream). ``mix_out``'s backward WRITES the rows' cotangent
through ``H_res`` (the simpler rule of the two ISSUE 57 names: ``n d`` more
a token than the least count's ``(5n + 3) d``, and each custom_vjp is its
function's own).

The backward of ``mix_in`` keeps of the forward ``z`` (``phi``'s product
times ``rsqrt``, ``[n^2 + 2n, tokens]``) and ``rsqrt`` itself, runs the
iterations again inside the call keeping every half step's matrix in VMEM,
and goes back through them, the sigmoids, ``rsqrt`` and the product
(``d(g phi)`` a row as ``dz^T X`` on the MXU, gathered over the tiles in a
block the call keeps; ``db`` and ``dalpha`` as 128 lanes' partial sums,
folded once outside).

The forward rule names ``h``, the coefficients, ``z`` and ``rsqrt``
``hyper.RESIDUAL_NAMES``: a remat block that saves by those names
(``llama.remat_block``) does not run ``mix_in``'s call a second time.

Precision: rows and the two products' operands in the stream's dtype, all
else float32, a row rounded once as it is written; no iteration dropped.
Calls: ``mhc_<in|out>_<fwd|bwd>_n<n>_t<tokens>_d<d>``. On the chip ``d`` is
whole lanes of 128 and the tokens whole tiles; on the CPU the calls run
interpreted.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import hyper
from ray_tpu.ops.pallas import flash

F32 = jnp.float32
LANES = 128
#: tokens a grid step holds of every row, where they divide the tokens
TILES = (256, 128)
#: tokens a trip of a pass's loop (whole tiles of a 16-bit stream)
_SUB = 32
_WHOLE = pl.BlockSpec(memory_space=pltpu.VMEM)


class Rule(NamedTuple):
    """A half's constants (``MoEConfig.hc_*``, ``norm_eps``)."""
    iters: int
    eps: float
    clamp: Tuple[float, float]
    norm_eps: float


def tile_tokens(tokens: int):
    """Tokens a grid step, or None where no tile divides them."""
    return next((t for t in TILES if tokens % t == 0), None)


def bytes_moved(n: int, d: int, itemsize: int) -> Tuple[int, int]:
    """(forward, backward) bytes a token and half layer the four calls'
    blocks move: the rows' slabs as ``hyper.stream_bytes`` counts them plus
    the rows' cotangent through ``H_res`` written and read (``2 n d``), and
    the float32 coefficients, 512 B a pass (written and read forward; read,
    their cotangent written and read backward), with ``z`` and ``rsqrt``
    (written forward, read backward). Nothing that remat runs again."""
    small = 4 * (hyper.columns(n) + 1)
    return ((3 * n + 2) * d * itemsize + 2 * 4 * LANES + small,
            (7 * n + 3) * d * itemsize + 3 * 4 * LANES + small)


# ------------------------------------------------------------ shared parts

def _fold(p):
    """[sub, d] -> [sub, 128]: the lane tiles' sum, on the VPU."""
    return sum(p[:, k:k + LANES] for k in range(0, p.shape[1], LANES))


def _sum_d(a, b, k: int, lane):
    """The sum over d of ``a b`` [sub, d] a token, on lane ``k`` of a
    [sub, 128] of zeros (``lane``: the lanes' iota): the lane tiles folded
    on the VPU, then one sum along the lanes a vreg."""
    return jnp.where(lane == k,
                     jnp.sum(_fold(a * b), axis=-1, keepdims=True), 0.0)


def _sinkhorn(m, iters: int, eps: float, keep=None):
    """``m``: n slabs [n, tile] (slab i, sublane j: M[i, j]) -> the slabs
    after ``iters`` times the columns and then the rows divided by their
    sums. ``keep``: a list every half step's slabs are appended to, the
    first ones included."""
    for _ in range(iters):
        if keep is not None:
            keep.append(m)
        r = 1.0 / (sum(m) + eps)                           # a column's, [n, tile]
        m = [mi * r for mi in m]
        if keep is not None:
            keep.append(m)
        m = [mi * (1.0 / (jnp.sum(mi, axis=0, keepdims=True) + eps))
             for mi in m]
    return m


def _sinkhorn_back(dm, kept, last, eps: float):
    """The iterations' pull-back: ``dm`` the cotangent of ``last`` (the
    slabs ``_sinkhorn`` returned), ``kept`` what it kept. Of ``y = m r``,
    ``r = 1 / (S(m) + eps)``: ``dm = (dy - S(dy y)) r``."""
    y = last
    for step in range(len(kept) - 1, -1, -1):
        m = kept[step]
        if step % 2:    # the rows' half step
            dm = [(di - jnp.sum(di * yi, axis=0, keepdims=True))
                  * (1.0 / (jnp.sum(mi, axis=0, keepdims=True) + eps))
                  for di, yi, mi in zip(dm, y, m)]
        else:           # the columns'
            r = 1.0 / (sum(m) + eps)
            s = sum(di * yi for di, yi in zip(dm, y))
            dm = [(di - s) * r for di in dm]
        y = m
    return dm


def _activations(z, a, b, n: int, clamp):
    """z [c, tile], a, b [c, 1] -> (sigmoid of the pre part [n, tile], of
    the post part [n, tile], the n slabs ``exp(clip(.))`` [n, tile], the
    slabs' pre-activations)."""
    act = a * z + b
    res = [act[2 * n + i * n:2 * n + (i + 1) * n] for i in range(n)]
    return (jax.nn.sigmoid(act[:n]), jax.nn.sigmoid(act[n:2 * n]),
            [jnp.exp(jnp.clip(r, *clamp)) for r in res], res)


def _trips(tile: int, body):
    sub = min(_SUB, tile)

    def trip(r, carry):
        body(pl.ds(pl.multiple_of(r * sub, sub), sub))
        return carry

    jax.lax.fori_loop(0, tile // sub, trip, 0)


# ---------------------------------------------------------------- kernels

def _in_fwd_kernel(x_ref, w_ref, a_ref, b_ref, h_ref, coef_ref, z_ref,
                   inv_ref, turn_ref, sq_ref, *, rule: Rule):
    n, tile, d = x_ref.shape

    def squares(rows):
        sq_ref[rows, :] = sum(
            _fold(jnp.square(x_ref[i, rows, :].astype(F32))) for i in range(n))

    _trips(tile, squares)
    ms = jnp.sum(sq_ref[...].T, axis=0, keepdims=True) / (n * d)   # [1, tile]
    inv = jax.lax.rsqrt(ms + rule.norm_eps)
    raw = sum(jax.lax.dot_general(w_ref[i], x_ref[i], flash._NT,
                                  preferred_element_type=F32)
              for i in range(n))                                   # [c, tile]
    z = raw * inv
    pre, post, m, _ = _activations(z, a_ref[...], b_ref[...], n, rule.clamp)
    m = _sinkhorn(m, rule.iters, rule.eps)
    z_ref[...], inv_ref[...] = z, inv
    turn_ref[...] = jnp.zeros(turn_ref.shape, F32)
    turn_ref[0:n, :], turn_ref[n:2 * n, :] = pre, 2.0 * post
    for i in range(n):
        turn_ref[2 * n + i * n:2 * n + (i + 1) * n, :] = m[i]
    coef_ref[...] = turn_ref[...].T                                # [tile, 128]

    def mix(rows):
        co = coef_ref[rows, :]
        h_ref[rows, :] = sum(co[:, i:i + 1] * x_ref[i, rows, :].astype(F32)
                             for i in range(n)).astype(h_ref.dtype)

    _trips(tile, mix)


def _out_fwd_kernel(x_ref, y_ref, coef_ref, o_ref):
    n, tile, _ = x_ref.shape

    def mix(rows):
        co = coef_ref[rows, :]
        xs = [x_ref[j, rows, :].astype(F32) for j in range(n)]
        y = y_ref[rows, :].astype(F32)
        for i in range(n):
            at = 2 * n + i * n
            o_ref[i, rows, :] = (
                sum(co[:, at + j:at + j + 1] * xs[j] for j in range(n))
                + co[:, n + i:n + i + 1] * y).astype(o_ref.dtype)

    _trips(tile, mix)


def _out_bwd_kernel(g_ref, x_ref, y_ref, coef_ref, dy_ref, dx_ref, dcoef_ref):
    n, tile, _ = x_ref.shape

    def back(rows):
        co = coef_ref[rows, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, co.shape, 1)
        gs = [g_ref[i, rows, :].astype(F32) for i in range(n)]
        xs = [x_ref[j, rows, :].astype(F32) for j in range(n)]
        y = y_ref[rows, :].astype(F32)
        dy_ref[rows, :] = sum(co[:, n + i:n + i + 1] * gs[i]
                              for i in range(n)).astype(dy_ref.dtype)
        for j in range(n):
            dx_ref[j, rows, :] = sum(
                co[:, 2 * n + i * n + j:2 * n + i * n + j + 1] * gs[i]
                for i in range(n)).astype(dx_ref.dtype)
        dcoef_ref[rows, :] = sum(
            _sum_d(gs[i], y, n + i, lane)
            + sum(_sum_d(gs[i], xs[j], 2 * n + i * n + j, lane)
                  for j in range(n)) for i in range(n))

    _trips(tile, back)


def _in_bwd_kernel(dh_ref, x_ref, dxs_ref, dcoef_ref, z_ref, inv_ref, w_ref,
                   a_ref, b_ref, dx_ref, dw_ref, dab_ref, turn_ref, cols_ref,
                   dact_ref, *, rule: Rule):
    n, tile, d = x_ref.shape
    c = hyper.columns(n)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, F32)
        dab_ref[...] = jnp.zeros(dab_ref.shape, F32)

    # H_pre's cotangent: the sums over d of dh X[i], beside what came in
    def pre_sums(rows):
        dco = dcoef_ref[rows, :]
        lane = jax.lax.broadcasted_iota(jnp.int32, dco.shape, 1)
        dh = dh_ref[rows, :].astype(F32)
        cols_ref[rows, :] = dco + sum(
            _sum_d(dh, x_ref[i, rows, :].astype(F32), i, lane)
            for i in range(n))

    _trips(tile, pre_sums)
    dco = cols_ref[...].T                                          # [128, tile]
    z, inv, a = z_ref[...], inv_ref[...], a_ref[...]
    pre, post, m0, res = _activations(z, a, b_ref[...], n, rule.clamp)
    kept = []
    last = _sinkhorn(m0, rule.iters, rule.eps, kept)
    dm = _sinkhorn_back(
        [dco[2 * n + i * n:2 * n + (i + 1) * n] for i in range(n)], kept,
        last, rule.eps)
    dact_ref[0:n, :] = dco[0:n] * pre * (1.0 - pre)
    dact_ref[n:2 * n, :] = dco[n:2 * n] * 2.0 * post * (1.0 - post)
    lo, hi = rule.clamp
    for i in range(n):
        dact_ref[2 * n + i * n:2 * n + (i + 1) * n, :] = jnp.where(
            (res[i] >= lo) & (res[i] <= hi), dm[i] * m0[i], 0.0)
    dact = dact_ref[...]                                           # [c, tile]
    dab_ref[0] += _fold(dact)
    dab_ref[1] += _fold(dact * z)
    dz = a * dact
    draw = dz * inv
    # rsqrt's and the mean's: the rows' own share, a factor a token
    own = -jnp.sum(dz * z, axis=0, keepdims=True) * inv * inv / (n * d)
    low = draw.astype(x_ref.dtype)
    for i in range(n):
        dw_ref[i] += jax.lax.dot_general(low, x_ref[i], flash._NN,
                                         preferred_element_type=F32)
    # tokens on the sublanes: pre and the rows' factor a lane each, and the
    # product's cotangent from lane 8 on, against ``w`` laid from row 8 on
    turn_ref[...] = jnp.zeros(turn_ref.shape, F32)
    turn_ref[0:n, :], turn_ref[n:n + 1, :] = pre, own
    turn_ref[8:8 + c, :] = draw
    cols_ref[...] = turn_ref[...].T

    def back(rows):
        co = cols_ref[rows, :]
        dh = dh_ref[rows, :].astype(F32)
        low = co.astype(x_ref.dtype)
        for i in range(n):
            dx_ref[i, rows, :] = (
                dxs_ref[i, rows, :].astype(F32) + co[:, i:i + 1] * dh
                + co[:, n:n + 1] * x_ref[i, rows, :].astype(F32)
                + jax.lax.dot_general(low, w_ref[i], flash._NN,
                                      preferred_element_type=F32)
            ).astype(dx_ref.dtype)

    _trips(tile, back)


# ---------------------------------------------------------------- the calls

def _specs(n: int, tile: int, d: int):
    """The blocks of one shape, ``tile`` tokens a grid step: the rows' [n,
    tile, d], a row's [tile, d], the coefficients' [tile, 128], what is
    small with the tokens on the lanes [k, tile], and a block the call
    keeps over the grid."""
    return dict(
        rows=pl.BlockSpec((n, tile, d), lambda t: (0, t, 0)),
        row=pl.BlockSpec((tile, d), lambda t: (t, 0)),
        coef=pl.BlockSpec((tile, LANES), lambda t: (t, 0)),
        small=lambda k: pl.BlockSpec((k, tile), lambda t: (0, t)),
        kept=lambda *shape: pl.BlockSpec(shape, lambda t: (0,) * len(shape)))


def _params(slabs: int, tile: int, d: int, itemsize: int, extra: int = 0,
            sums: bool = False):
    """``slabs`` [tile, d] blocks in flight twice over, a trip's float32
    temporaries and ``extra`` bytes of scratch and kept blocks; ``sums``:
    the call gathers over the tiles in a block it keeps, so they are walked
    in order."""
    need = (2 * slabs * tile * d * itemsize + extra
            + 24 * min(_SUB, tile) * d * 4 + (4 << 20))
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary" if sums else "parallel",),
        vmem_limit_bytes=max(flash._VMEM_DEFAULT_LIMIT_BYTES, need))


def _tag(n: int, tokens: int, d: int) -> str:
    return f"n{n}_t{tokens}_d{d}"


def _a_column(alpha, n: int):
    """``alpha`` [3] -> [c, 1]: a column's own scale."""
    return jnp.repeat(alpha.astype(F32), jnp.array([n, n, n * n]),
                      total_repeat_length=hyper.columns(n))[:, None]


@functools.partial(jax.jit, static_argnames=("rule", "tile", "interpret"))
def _in_fwd_call(x, w, a, b, *, rule: Rule, tile: int, interpret: bool):
    n, tokens, d = x.shape
    sp, c = _specs(n, tile, d), hyper.columns(n)
    return pl.pallas_call(
        functools.partial(_in_fwd_kernel, rule=rule),
        grid=(tokens // tile,),
        in_specs=[sp["rows"], _WHOLE, _WHOLE, _WHOLE],
        out_specs=[sp["row"], sp["coef"], sp["small"](c), sp["small"](1)],
        out_shape=[jax.ShapeDtypeStruct((tokens, d), x.dtype),
                   jax.ShapeDtypeStruct((tokens, LANES), F32),
                   jax.ShapeDtypeStruct((c, tokens), F32),
                   jax.ShapeDtypeStruct((1, tokens), F32)],
        scratch_shapes=[pltpu.VMEM((LANES, tile), F32),
                        pltpu.VMEM((tile, LANES), F32)],
        compiler_params=_params(n + 1, tile, d, x.dtype.itemsize,
                                extra=2 * n * c * d * x.dtype.itemsize),
        interpret=interpret, name=f"mhc_in_fwd_{_tag(n, tokens, d)}",
    )(x, w, a, b)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _out_fwd_call(x, y, coef, *, tile: int, interpret: bool):
    n, tokens, d = x.shape
    sp = _specs(n, tile, d)
    return pl.pallas_call(
        _out_fwd_kernel, grid=(tokens // tile,),
        in_specs=[sp["rows"], sp["row"], sp["coef"]], out_specs=sp["rows"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=_params(2 * n + 1, tile, d, x.dtype.itemsize),
        interpret=interpret, name=f"mhc_out_fwd_{_tag(n, tokens, d)}",
    )(x, y, coef)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _out_bwd_call(g, x, y, coef, *, tile: int, interpret: bool):
    n, tokens, d = x.shape
    sp = _specs(n, tile, d)
    return pl.pallas_call(
        _out_bwd_kernel, grid=(tokens // tile,),
        in_specs=[sp["rows"], sp["rows"], sp["row"], sp["coef"]],
        out_specs=[sp["row"], sp["rows"], sp["coef"]],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(coef.shape, F32)],
        compiler_params=_params(3 * n + 2, tile, d, x.dtype.itemsize),
        interpret=interpret, name=f"mhc_out_bwd_{_tag(n, tokens, d)}",
    )(g, x, y, coef)


@functools.partial(jax.jit, static_argnames=("rule", "tile", "interpret"))
def _in_bwd_call(dh, x, dxs, dcoef, z, inv, w, a, b, *, rule: Rule, tile: int,
                 interpret: bool):
    n, tokens, d = x.shape
    sp, c = _specs(n, tile, d), hyper.columns(n)
    # ``w`` from row 8 of 128 on, where the turned cotangent's lanes lie
    wide = jnp.zeros((n, LANES, d), w.dtype).at[:, 8:8 + c].set(w)
    return pl.pallas_call(
        functools.partial(_in_bwd_kernel, rule=rule),
        grid=(tokens // tile,),
        in_specs=[sp["row"], sp["rows"], sp["rows"], sp["coef"],
                  sp["small"](c), sp["small"](1), _WHOLE, _WHOLE, _WHOLE],
        out_specs=[sp["rows"], sp["kept"](n, c, d), sp["kept"](2, c, LANES)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, c, d), F32),
                   jax.ShapeDtypeStruct((2, c, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((LANES, tile), F32),
                        pltpu.VMEM((tile, LANES), F32),
                        pltpu.VMEM((c, tile), F32)],
        compiler_params=_params(
            3 * n + 1, tile, d, x.dtype.itemsize, sums=True,
            extra=2 * n * d * (LANES * x.dtype.itemsize + 4 * c)),
        interpret=interpret, name=f"mhc_in_bwd_{_tag(n, tokens, d)}",
    )(dh, x, dxs, dcoef, z, inv, wide, a, b)


# ------------------------------------------------------------- the two mixes

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def mix_in(x: jax.Array, w: jax.Array, b: jax.Array, alpha: jax.Array,
           rule: Rule, tile: int):
    """``x`` [n, tokens, d] the rows; ``w`` [n, n^2 + 2n, d] float32, ``g``
    folded into ``phi`` and turned; ``b`` [n^2 + 2n], ``alpha`` [3] float32.
    Returns (``h`` [tokens, d]; the coefficients [tokens, 128] float32, a
    lane each: ``H_pre`` n, ``H_post`` n, ``H_res`` row by row n^2, zeros;
    the rows themselves, for ``mix_out`` to read). Differentiable in all
    four."""
    return _in_fwd(x, w, b, alpha, rule, tile)[0]


def _in_fwd(x, w, b, alpha, rule, tile):
    n = x.shape[0]
    h, coef, z, inv = map(checkpoint_name, _in_fwd_call(
        x, w.astype(x.dtype), _a_column(alpha, n), b.astype(F32)[:, None],
        rule=rule, tile=tile, interpret=flash._needs_interpret()), hyper.RESIDUAL_NAMES)
    return (h, coef, x), (x, w, b, alpha, z, inv)


def _in_bwd(rule, tile, kept, cotangents):
    x, w, b, alpha, z, inv = kept
    dh, dcoef, dxs = cotangents
    n = x.shape[0]
    dx, dw, dab = _in_bwd_call(
        dh, x, dxs, dcoef, z, inv, w.astype(x.dtype), _a_column(alpha, n),
        b.astype(F32)[:, None], rule=rule, tile=tile,
        interpret=flash._needs_interpret())
    db, dscaled = dab.sum(-1)
    dalpha = jnp.stack([dscaled[:n].sum(), dscaled[n:2 * n].sum(),
                        dscaled[2 * n:].sum()])
    return dx, dw.astype(w.dtype), db.astype(b.dtype), dalpha.astype(alpha.dtype)


mix_in.defvjp(_in_fwd, _in_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def mix_out(x: jax.Array, y: jax.Array, coef: jax.Array, tile: int):
    """``x`` [n, tokens, d] (``mix_in``'s third result), the branch ``y``
    [tokens, d], ``mix_in``'s coefficients -> the rows after the half."""
    return _out_fwd_call(x, y, coef, tile=tile,
                         interpret=flash._needs_interpret())


def _out_fwd(x, y, coef, tile):
    return _out_fwd_call(x, y, coef, tile=tile,
                         interpret=flash._needs_interpret()), (x, y, coef)


def _out_bwd(tile, kept, g):
    dy, dx, dcoef = _out_bwd_call(g, *kept, tile=tile,
                                  interpret=flash._needs_interpret())
    return dx, dy, dcoef


mix_out.defvjp(_out_fwd, _out_bwd)
