"""EVA attention as Pallas TPU kernels, forward and backward: one softmax
over two sets of keys.

A query at position t, in window ``W(t) = t // window``, sees

- the keys of its own window at or before it (a block-diagonal causal part:
  ``window`` x ``window`` blocks down the diagonal of the [s, s] square), and
- one summary a ``chunk`` of every EARLIER window (``ops/eva.summaries``:
  ``window // chunk`` of them a window), a part that is strictly
  block-lower-triangular at ``window`` queries x ``window // chunk``
  summaries and needs no mask,

under ONE softmax. The choice ISSUE 52 left open is taken the second way: one
call walks, for a block of queries, the summary blocks of the windows before
its own (a prefix whose length differs by window) and then the causal blocks
of its own window, with one running (max, sum, accumulator) over both, as
``ops/pallas/flash.py`` keeps over its keys. Two flash calls merged by their
log-sum-exps would have needed the summary part as a call a window (their
key lengths differ) or a mask the flash kernels do not have, and a pass over
``o`` to join them.

What is visited. The grid's second axis is not (q block, k block) but a list
of VISITS, made on the host from the shape alone (``visits``) and handed to
the kernel as scalar-prefetch arrays that its block index maps read: which q
block, which summary block, which local key block, what kind (a summary
block, a local block wholly below the diagonal, the block the diagonal
crosses), and whether it is the q block's first or last. A tile that holds
no visible pair is in no list: the kernels visit exactly the tiles that hold
one (``plan``: ``tiles_visited`` = ``tiles_needed``), window 0 has no
summary visit, and a sequence of one window makes no summary-gradient call.
An operand a visit does not read keeps the block index it had, so it is not
fetched again.

Blocks. Queries and local keys in blocks of ``min(window, 1024)`` rows (the
tile ``flash.py`` found fastest; ``window`` must be a whole number of them),
summaries in blocks of one window's (``window // chunk``). The diagonal
block is masked whole (rows against keys by two iotas); every other visit
runs the clear body.

The backward is three calls: ``dq`` over the forward's visits; ``dkv`` over
the local key blocks (a key block's visits are the q blocks of its window at
or after it); ``dsum`` over the summary blocks (a window's summaries are
visited by every q block of every later window; the last window's get no
gradient and no visit). ``dkv`` and ``dsum`` are one kernel body on the
transposed tile [keys, rows], as ``flash._dkv_kernel``. All read the
forward's ``o`` and ``lse`` (named by ``flash.RESIDUAL_NAMES``, so a remat
block that keeps those keeps these).

Names: ``eva_attn_<fwd|dq|dkv|dsum>_bh<b*h>_s<seq>_d<d>_w<window>_c<chunk>``,
the sequence as padded to whole windows.

Precision: operands in the inputs' dtype, float32 accumulation and softmax,
as ``flash.py``.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.ops.pallas import flash
from ray_tpu.ops.pallas.flash import _NN, _NT, _dot

KINDS = ("fwd", "dq", "dkv", "dsum")
_TARGET_BLOCK = 1024
# a visit's kind, as the kernels read it
SUMMARY, CLEAR, DIAGONAL = 0, 1, 2


def block_rows(window: int) -> int:
    """Rows of a q block and of a local key block."""
    block = min(window, _TARGET_BLOCK)
    if window % block:
        raise ValueError(f"eva window {window} is not a whole number of "
                         f"{block}-row blocks")
    return block


def visits(n_windows: int, blocks_per_window: int, kind: str
           ) -> Dict[str, np.ndarray]:
    """The visits of one head, in grid order, as int32 arrays a name each.

    ``fwd`` / ``dq``: q blocks outer. ``q`` the q block, ``s`` the summary
    block and ``l`` the local key block the visit reads or holds, ``kind``
    (``SUMMARY``, ``CLEAR``, ``DIAGONAL``), ``first`` / ``last`` of the q
    block's visits. ``dkv``: local key blocks outer, ``k`` the key block,
    ``q`` the q block, ``kind`` ``CLEAR`` or ``DIAGONAL``. ``dsum``:
    summary blocks outer (all but the last window's), every ``kind``
    ``CLEAR``."""
    bpw, rows = blocks_per_window, []

    def group(mine):  # one outer block's visits, the first and last flagged
        rows.extend((*v, int(n == 0), int(n == len(mine) - 1))
                    for n, v in enumerate(mine))

    if kind in ("fwd", "dq"):
        names = ("q", "s", "l", "kind", "first", "last")
        for i in range(n_windows * bpw):
            w, r = divmod(i, bpw)
            group([(i, j, w * bpw, SUMMARY) for j in range(w)]
                  + [(i, max(w - 1, 0), w * bpw + c,
                      DIAGONAL if c == r else CLEAR) for c in range(r + 1)])
    elif kind == "dkv":
        names = ("k", "q", "kind", "first", "last")
        for j in range(n_windows * bpw):
            w, c = divmod(j, bpw)
            group([(j, w * bpw + r, DIAGONAL if r == c else CLEAR)
                   for r in range(c, bpw)])
    elif kind == "dsum":
        names = ("k", "q", "kind", "first", "last")
        for j in range(n_windows - 1):
            group([(j, i, CLEAR)
                   for i in range((j + 1) * bpw, n_windows * bpw)])
    else:
        raise ValueError(f"kind {kind!r} is not one of {KINDS}")
    table = np.asarray(rows, np.int32).reshape(len(rows), len(names))
    return {name: table[:, n] for n, name in enumerate(names)}


def tiles_needed(n_windows: int, blocks_per_window: int) -> Dict[str, int]:
    """(q block, key block) tiles of one head that hold a visible pair, by
    the part they lie in: what any tiling of these block sizes has to
    visit."""
    bpw = blocks_per_window
    return {"summary": bpw * n_windows * (n_windows - 1) // 2,
            "local": n_windows * bpw * (bpw + 1) // 2}


def _name(kind: str, bh: int, s: int, d: int, window: int, chunk: int) -> str:
    return f"eva_attn_{kind}_bh{bh}_s{s}_d{d}_w{window}_c{chunk}"


def _params(kind: str, block: int, d: int, itemsize: int):
    # a visit holds what the flash kernel of its kind holds at this tile
    need = flash._vmem_bytes(kind if kind in ("fwd", "dq") else "dkv",
                             block, block, d, itemsize)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=max(flash._VMEM_DEFAULT_LIMIT_BYTES, 2 * need))


def _lower_left(shape, q_axis: int):
    """Of a square tile whose rows lie on ``q_axis``: key at or before row."""
    return (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            >= jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


def _by_kind(kind_ref, t, step, summary, local):
    """Run ``step(keys, values, masked)`` on what visit ``t`` reads."""
    kind = kind_ref[t]
    if summary is not None:
        pl.when(kind == SUMMARY)(lambda: step(*summary, False))
    pl.when(kind == CLEAR)(lambda: step(*local, False))
    pl.when(kind == DIAGONAL)(lambda: step(*local, True))


# ---------------------------------------------------------------- forward

def _fwd_kernel(qi, si, li, kind, first, last, q_ref, k_ref, v_ref, ks_ref,
                vs_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale):
    t = pl.program_id(1)

    @pl.when(first[t] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(k_at, v_at, masked):
        # every row of a visit has a live key (the diagonal block's row its
        # own), so the running maximum is finite after a row's first visit
        s = _dot(q_ref[0], k_at[0], _NT) * scale           # [rows, keys]
        if masked:
            s = jnp.where(_lower_left(s.shape, 0), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + _dot(
            p.astype(v_at.dtype), v_at[0], _NN)

    _by_kind(kind, t, step, (ks_ref, vs_ref), (k_ref, v_ref))

    @pl.when(last[t] == 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _q_major_specs(block: int, per_window: int, d: int):
    """Block specs of a (bh, visit) grid walked q blocks outer: a q-side
    block, a local key block, a summary block."""
    def q(cols):
        return pl.BlockSpec((1, block, cols),
                            lambda b, t, qi, si, li, *_: (b, qi[t], 0))
    local = pl.BlockSpec((1, block, d),
                         lambda b, t, qi, si, li, *_: (b, li[t], 0))
    summary = pl.BlockSpec((1, per_window, d),
                           lambda b, t, qi, si, li, *_: (b, si[t], 0))
    return q, local, summary


def _fwd(q, k, v, ks, vs, *, scale, window, chunk, interpret):
    bh, s, d = q.shape
    block, per_window = block_rows(window), window // chunk
    sched = visits(s // window, window // block, "fwd")
    qspec, local, summary = _q_major_specs(block, per_window, d)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched),
            grid=(bh, len(sched["q"])),
            in_specs=[qspec(d), local, local, summary, summary],
            out_specs=[qspec(d), qspec(1)],
            scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, 1), jnp.float32),
                            pltpu.VMEM((block, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        compiler_params=_params("fwd", block, d, q.dtype.itemsize),
        interpret=interpret,
        name=_name("fwd", bh, s, d, window, chunk),
    )(*sched.values(), q, k, v, ks, vs)
    return o, lse[..., 0]


# ---------------------------------------------------------------- backward

def _dq_kernel(qi, si, li, kind, first, last, q_ref, k_ref, v_ref, ks_ref,
               vs_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale):
    t = pl.program_id(1)

    @pl.when(first[t] == 1)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(k_at, v_at, masked):
        k = k_at[0]
        s = _dot(q_ref[0], k, _NT) * scale                 # [rows, keys]
        p = jnp.exp(s - lse_ref[0])
        if masked:
            p = jnp.where(_lower_left(s.shape, 0), p, 0.0)
        dp = _dot(do_ref[0], v_at[0], _NT)
        ds = p * (dp - delta_ref[0])
        dq_scr[...] += _dot(ds.astype(k.dtype), k, _NN)

    _by_kind(kind, t, step, (ks_ref, vs_ref), (k_ref, v_ref))

    @pl.when(last[t] == 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(ki, qi, kind, first, last, q_ref, k_ref, v_ref, do_ref,
                lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale):
    """One key block (local keys, or summaries) against the q blocks that
    see it, on the transposed tile [keys, rows] (``flash._dkv_kernel``)."""
    t = pl.program_id(1)

    @pl.when(first[t] == 1)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(k_at, v_at, masked):
        q, do = q_ref[0], do_ref[0]
        s = _dot(k_at[0], q, _NT) * scale                  # [keys, rows]
        p = jnp.exp(s - lse_ref[0])                        # lse [1, rows]
        if masked:
            p = jnp.where(_lower_left(s.shape, 1), p, 0.0)
        dv_scr[...] += _dot(p.astype(do.dtype), do, _NN)
        dp = _dot(v_at[0], do, _NT)
        ds = p * (dp - delta_ref[0])
        dk_scr[...] += _dot(ds.astype(q.dtype), q, _NN)

    _by_kind(kind, t, step, None, (k_ref, v_ref))

    @pl.when(last[t] == 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _key_grads(kind, q, keys, values, do, lse, delta, *, key_block, n_keys,
               scale, window, chunk, interpret):
    """``dkv`` or ``dsum``: the gradients of the first ``n_keys`` rows of
    ``keys`` / ``values`` [bh, ., d], in blocks of ``key_block``."""
    bh, s, d = q.shape
    block = block_rows(window)
    sched = visits(s // window, window // block, kind)
    kspec = pl.BlockSpec((1, key_block, d), lambda b, t, ki, qi, *_: (b, ki[t], 0))
    qspec = pl.BlockSpec((1, block, d), lambda b, t, ki, qi, *_: (b, qi[t], 0))
    row = pl.BlockSpec((1, 1, block), lambda b, t, ki, qi, *_: (b, 0, qi[t]))
    return pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched),
            grid=(bh, len(sched["k"])),
            in_specs=[qspec, kspec, kspec, qspec, row, row],
            out_specs=[kspec, kspec],
            scratch_shapes=[pltpu.VMEM((key_block, d), jnp.float32),
                            pltpu.VMEM((key_block, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, n_keys, d), keys.dtype),
                   jax.ShapeDtypeStruct((bh, n_keys, d), values.dtype)],
        compiler_params=_params(kind, block, d, q.dtype.itemsize),
        interpret=interpret,
        name=_name(kind, bh, s, d, window, chunk),
    )(*sched.values(), q, keys, values, do, lse[:, None], delta[:, None])


def _bwd(q, k, v, ks, vs, o, lse, do, *, scale, window, chunk, interpret):
    bh, s, d = q.shape
    block, per_window = block_rows(window), window // chunk
    n_windows = s // window
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    static = dict(scale=scale, window=window, chunk=chunk, interpret=interpret)

    sched = visits(n_windows, window // block, "dq")
    qspec, local, summary = _q_major_specs(block, per_window, d)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(sched),
            grid=(bh, len(sched["q"])),
            in_specs=[qspec(d), local, local, summary, summary, qspec(d),
                      qspec(1), qspec(1)],
            out_specs=[qspec(d)],
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        compiler_params=_params("dq", block, d, q.dtype.itemsize),
        interpret=interpret,
        name=_name("dq", bh, s, d, window, chunk),
    )(*sched.values(), q, k, v, ks, vs, do, lse[..., None], delta[..., None])[0]

    dk, dv = _key_grads("dkv", q, k, v, do, lse, delta, key_block=block,
                        n_keys=s, **static)
    if n_windows == 1:  # no query sees a summary: no call for an empty set
        return dq, dk, dv, jnp.zeros_like(ks), jnp.zeros_like(vs)
    seen = (n_windows - 1) * per_window
    dks, dvs = _key_grads("dsum", q, ks, vs, do, lse, delta,
                          key_block=per_window, n_keys=seen, **static)
    unseen = ((0, 0), (0, ks.shape[1] - seen), (0, 0))
    return dq, dk, dv, jnp.pad(dks, unseen), jnp.pad(dvs, unseen)


# ---------------------------------------------------------------- public API

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _core(q, k, v, ks, vs, scale, window, chunk, interpret):
    return _core_fwd(q, k, v, ks, vs, scale, window, chunk, interpret)[0]


def _core_fwd(q, k, v, ks, vs, scale, window, chunk, interpret):
    o, lse = _fwd(q, k, v, ks, vs, scale=scale, window=window, chunk=chunk,
                  interpret=interpret)
    o, lse = map(checkpoint_name, (o, lse), flash.RESIDUAL_NAMES)
    return o, (q, k, v, ks, vs, o, lse)


def _core_bwd(scale, window, chunk, interpret, res, do):
    return _bwd(*res, do, scale=scale, window=window, chunk=chunk,
                interpret=interpret)


_core.defvjp(_core_fwd, _core_bwd)


def eva_attend(q: jax.Array, k: jax.Array, v: jax.Array, ks: jax.Array,
               vs: jax.Array, *, window: int, chunk: int, scale: float,
               interpret: bool = None) -> jax.Array:
    """q, k, v [bh, s, d] and the summaries ks, vs [bh, s // chunk, d], the
    sequence a whole number of windows -> o [bh, s, d]. Differentiable in
    all five."""
    bh, s, d = q.shape
    if s % window or window % chunk or ks.shape[1] * chunk != s:
        raise ValueError(
            f"eva_attend takes whole windows: s {s}, window {window}, chunk "
            f"{chunk}, {ks.shape[1]} summaries")
    if interpret is None:
        interpret = flash._needs_interpret()
    return _core(q, k, v, ks, vs, scale, window, chunk, interpret)


def tiling(seq: int, window: int) -> Tuple[int, int, int]:
    """(windows, blocks a window, rows a block) of a padded sequence."""
    block = block_rows(window)
    return -(-seq // window), window // block, block
