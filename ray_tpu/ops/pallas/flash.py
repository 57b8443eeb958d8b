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
dynamic scalar (prefetched into SMEM, so the block indices can read it) so
ring attention can slide the causal mask per step.

How blocks are chosen. A grid step costs a fixed ~0.4 us before it multiplies
anything, so the tile decides the kernel's speed: at 128 x 128 a step's two
products are a tenth of that (32,768 steps a call at s 4096: 18.8 / 14.0 /
17.7 ms for fwd / dq / dkv at bh 32, d 128, bf16, causal on a v5e; at
1024 x 1024 1.60 / 2.07 / 2.27 ms, PR 27's chip run; 512 and 2048 a side are
slower for all three). ``block_q`` / ``block_k`` left at None are chosen per
kernel by ``plan`` from what the call can see: ``_TARGET_BLOCK`` rows a side,
halved until the tile's working set (operands twice for the pipeline, the
float32 scratch, the [block_q, block_k] float32 temporaries) is inside
``_VMEM_BUDGET_BYTES``, so a wider head or float32 operands shrink it; a
sequence shorter than the tile is one block of its own (padded) length. The
call raises Mosaic's scoped VMEM limit to what the plan counts. An explicit
``block_q=`` / ``block_k=`` wins for all three kernels (on the chip such a
block is a multiple of 128 rows or the whole padded sequence).

What is skipped. With the causal mask, a (q block, k block) pair above the
diagonal — by the RUNTIME offset — does nothing: no products, no ``exp``, and
no fetch, because the block index of the walked operand is clamped to the
last (in dkv: first) live block and the pipeline does not fetch an index
again. A pair wholly below the diagonal and inside both true lengths skips
the mask arithmetic; only the blocks the diagonal or a padded edge crosses
build the iota compare. Rows no key reaches give ``o`` = 0, ``lse`` = NEG_INF.

The band. With ``window`` w (causal only) a query at position p sees the keys
in (p - w, p]. A pair wholly below the band is skipped like one above the
diagonal, from the other side: the walked index is clamped to the first (in
dkv: last) live block too, and the band's lower edge builds the mask where it
crosses a block. ``window=None`` is the causal kernel as it was.

Names. A call is named ``flash_<kind>_bh<bh>_q<sq>_k<sk>_d<d>_c<causal>_w<w>``
(w 0: no band), the true lengths before padding: a device trace shows a
``pallas_call`` under its name, and a call's result does not say what of
the square it skipped.

Precision. Products take their operands in the inputs' dtype and accumulate
in float32, forward and backward alike (``p`` and ``ds`` are cast as the
forward casts ``p``); the softmax statistics are float32. float32 inputs
multiply in float32.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Any, Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF

KINDS = ("fwd", "dq", "dkv")

_LANES = 128
# rows a side of the tile a kernel takes where the sequence and the budget
# allow it: the fastest of 256 ... 4096 for each of the three (module docstring)
_TARGET_BLOCK = 1024
# what a tile's working set may take of VMEM (v5e and v6e hold 128 MiB, v7x
# 64), and Mosaic's scoped default, which a call raises only if it must
_VMEM_BUDGET_BYTES = 40 << 20
_VMEM_DEFAULT_LIMIT_BYTES = 16 << 20
# [block_q, block_k] float32 temporaries a kernel's body holds at once
# (scores, weights, their casts, the mask's two iotas; the backward adds dp,
# ds), as if none shared a buffer: Mosaic fits the planned tile into half
_SCORE_TEMPS = {"fwd": 5, "dq": 7, "dkv": 7}


def _needs_interpret() -> bool:
    """Interpret mode is for the CPU tests and nothing else: every other
    backend compiles the kernel, or fails where it cannot."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------- the plan

class Plan(NamedTuple):
    """One kernel's tiling at one shape. ``grid_steps`` and ``live_steps``
    count (q block, k block) pairs of one head: all of them, and those that
    do work at ``q_offset`` 0."""
    kind: str
    block_q: int
    block_k: int
    grid_steps: int
    live_steps: int
    vmem_bytes: int
    vmem_limit_bytes: int


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pick_block(requested: int, seq: int) -> int:
    """An explicit block size, shrunk (to a multiple of 8) for short
    sequences so tiny shapes don't pad to it."""
    return min(requested, _round_up(max(seq, 8), 8))


def _fit_block(target: int, seq: int) -> int:
    """A planned block of at most ``target`` rows: the sequence cut into the
    fewest such blocks, each a multiple of 128; a sequence under 128 is one
    block of its own length."""
    if seq < _LANES:
        return _round_up(max(seq, 8), 8)
    n = -(-seq // target)
    return _round_up(-(-seq // n), _LANES)


def _vmem_bytes(kind: str, block_q: int, block_k: int, head_dim: int,
                itemsize: int) -> int:
    """What one grid step holds: each operand and result block twice (the
    pipeline's two buffers), a [rows, 1] float32 block padded to 128 lanes,
    the float32 scratch, and the score-sized temporaries."""
    d = _round_up(head_dim, _LANES)
    q_blk, k_blk = block_q * d * itemsize, block_k * d * itemsize
    q_col = block_q * _LANES * 4
    if kind == "fwd":    # q k v -> o lse | m l acc
        blocks = 2 * (2 * q_blk + 2 * k_blk + q_col)
        scratch = 2 * q_col + block_q * d * 4
    elif kind == "dq":   # q k v do lse delta -> dq | dq
        blocks = 2 * (3 * q_blk + 2 * k_blk + 2 * q_col)
        scratch = block_q * d * 4
    else:                # q k v do lse delta (rows) -> dk dv | dk dv
        blocks = 2 * (2 * q_blk + 4 * k_blk + 2 * 8 * block_q * 4)
        scratch = 2 * block_k * d * 4
    return blocks + scratch + _SCORE_TEMPS[kind] * block_q * block_k * 4


def _live(i, j, block_q: int, block_k: int, q_offset):
    """Does q block ``i`` see any key of k block ``j`` under the causal
    mask: the block's last row is at or after the block's first key."""
    return j * block_k <= i * block_q + block_q - 1 + q_offset


def _in_band(i, j, block_q: int, block_k: int, q_offset, window: int):
    """Does any key of k block ``j`` lie in the band of a row of q block
    ``i``: the block's first row is less than ``window`` after the block's
    last key."""
    return i * block_q + q_offset - (j * block_k + block_k - 1) < window


def _check_window(causal: bool, window: Optional[int]) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window {window!r} needs causal attention and at "
                         f"least one key a query")


def plan(seq_q: int, seq_k: int, head_dim: int, itemsize: int, causal: bool,
         kind: str, blocks: Optional[Tuple[int, int]] = None,
         window: Optional[int] = None) -> Plan:
    """The tiling of one kernel (``kind`` of ``KINDS``) at one shape; pure.
    Explicit ``blocks`` (block_q, block_k) are kept, shrunk to a short
    sequence. ``window`` moves no block's size: it only takes the pairs
    below the band out of ``live_steps``."""
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r} is not one of {KINDS}")
    _check_window(causal, window)
    if blocks is not None:
        bq, bk = _pick_block(blocks[0], seq_q), _pick_block(blocks[1], seq_k)
    else:
        tq = tk = _TARGET_BLOCK
        # halve the longer side until the tile fits
        while (_vmem_bytes(kind, tq, tk, head_dim, itemsize)
               > _VMEM_BUDGET_BYTES and max(tq, tk) > _LANES):
            tq, tk = (tq // 2, tk) if tq >= tk else (tq, tk // 2)
        bq, bk = _fit_block(tq, seq_q), _fit_block(tk, seq_k)
    nq, nk = -(-seq_q // bq), -(-seq_k // bk)
    live = sum(_live(i, j, bq, bk, 0)
               and (window is None or _in_band(i, j, bq, bk, 0, window))
               for i in range(nq) for j in range(nk)) if causal else nq * nk
    need = _vmem_bytes(kind, bq, bk, head_dim, itemsize)
    return Plan(kind, bq, bk, nq * nk, live, need,
                max(_VMEM_DEFAULT_LIMIT_BYTES, need))


_noting = threading.local()


@contextlib.contextmanager
def noting_plans(into: List[Dict[str, Any]]) -> Iterator[None]:
    """Within the scope, each distinct plan a flash kernel is traced with
    in this thread is appended to ``into`` (the plan's fields and the shape
    it was chosen for). A plan is static per traced shape, so the scope
    belongs round the call that traces: ``StepDriver`` puts it round its
    launches and hands the list to its recorder."""
    was = getattr(_noting, "into", None)
    _noting.into = into
    try:
        yield
    finally:
        _noting.into = was


def _planned(kind: str, q, k, causal: bool,
             blocks: Optional[Tuple[int, int]],
             window: Optional[int]) -> Tuple[Plan, str]:
    """The plan of the kernel about to be built on q, k [bh, s, d], noted,
    and the call's name (module docstring)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    p = plan(sq, sk, d, q.dtype.itemsize, causal, kind, blocks, window)
    into = getattr(_noting, "into", None)
    if into is not None:
        note = {**p._asdict(), "seq_q": sq, "seq_k": sk, "head_dim": d,
                "itemsize": q.dtype.itemsize, "causal": causal,
                "window": window}
        if note not in into:
            into.append(note)
    return p, (f"flash_{kind}_bh{bh}_q{sq}_k{sk}_d{d}_c{int(causal)}"
               f"_w{window or 0}")


# ------------------------------------------------------- what a step skips

def _on_live_steps(step, qi, kk, qoff, *, causal, window, block_q, block_k,
                   q_len, kv_len, q_axis):
    """Run ``step(mask_of)`` for the (q block, k block) pair, at most once:
    not at all if the causal mask or the band leaves the pair nothing; with
    ``mask_of`` None if no element of it is masked (every key at or before
    every row and inside every row's band, no padded row or key in it);
    else with the function that builds the mask of a tile: keys inside the
    true length and, causal, at or before their row and less than ``window``
    before it. ``q_axis`` is the axis rows lie on (1 in dkv's transposed
    tile)."""
    q_lo, k_lo = qi * block_q + qoff, kk * block_k
    clear = ((qi + 1) * block_q <= q_len) & (k_lo + block_k <= kv_len)
    live = True
    if causal:
        live = _live(qi, kk, block_q, block_k, qoff)
        clear = clear & (k_lo + block_k - 1 <= q_lo)
    if window is not None:
        live = live & _in_band(qi, kk, block_q, block_k, qoff, window)
        clear = clear & (q_lo + block_q - 1 - k_lo < window)

    def mask_of(shape):
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
        mask = kpos < kv_len
        if causal:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            mask = mask & (qpos >= kpos)
            if window is not None:
                mask = mask & (qpos - kpos < window)
        return mask

    pl.when(live & clear)(lambda: step(None))
    pl.when(live & jnp.logical_not(clear))(lambda: step(mask_of))


def _last_live_k(i, qoff, block_q, block_k, nk):
    """The last k block q block ``i`` sees (0 if none: the index has to name
    a block)."""
    last_row = jnp.maximum(i * block_q + block_q - 1 + qoff, 0)
    return jnp.minimum(last_row // block_k, nk - 1)


def _first_live_k(i, qoff, block_q, block_k, window):
    """The first k block inside the band of q block ``i``'s first row."""
    return jnp.maximum(i * block_q + qoff - window + 1, 0) // block_k


def _first_live_q(j, qoff, block_q, block_k, nq):
    """The first q block that sees k block ``j`` (the last if none)."""
    return jnp.minimum(jnp.maximum(j * block_k - qoff, 0) // block_q, nq - 1)


def _last_live_q(j, qoff, block_q, block_k, nq, window):
    """The last q block whose band still holds a key of k block ``j``."""
    last_row = jnp.maximum(j * block_k + block_k - 1 + window - 1 - qoff, 0)
    return jnp.minimum(last_row // block_q, nq - 1)


_NT = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))  # [m, n] x [n, d] -> [m, d]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- forward

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                 # outputs
                m_scr, l_scr, acc_scr,          # scratch
                *, scale, **tile):
    qi, kk = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(mask_of):
        s = _dot(q_ref[0], k_ref[0], _NT) * scale      # [bq, bk]
        if mask_of:
            s = jnp.where(mask_of(s.shape), s, NEG_INF)
        m_prev = m_scr[...]                            # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # A row no key has reached yet keeps m at NEG_INF, where
        # exp(s - m) = exp(0) would count its masked keys: exponentiate
        # against 0 there, so that p = exp(NEG_INF) = 0 and alpha = 0.
        m_exp = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new) if mask_of \
            else m_new
        alpha = jnp.exp(m_prev - m_exp)
        p = jnp.exp(s - m_exp)                         # [bq, bk] fp32
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + _dot(
            p.astype(v_ref.dtype), v_ref[0], _NN)

    _on_live_steps(step, qi, kk, qoff_ref[0], q_axis=0, **tile)

    @pl.when(kk == nk - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[...] + jnp.log(l_safe))
        lse_ref[0] = lse.astype(lse_ref.dtype)


def _compiler_params(p: Plan):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=p.vmem_limit_bytes)


def _walks_k(p: Plan, causal: bool, nk: int, window: Optional[int]):
    """Block specs of a (bh, nq, nk) grid: the q-side block of the step,
    and the k-side block, which past the last live one (and, with a band,
    before the first) stays where it is."""
    def k_index(b, i, j, qoff):
        if causal:
            last = _last_live_k(i, qoff[0], p.block_q, p.block_k, nk)
            j = jnp.minimum(j, last)
            if window is not None:
                j = jnp.maximum(j, jnp.minimum(last, _first_live_k(
                    i, qoff[0], p.block_q, p.block_k, window)))
        return b, j, 0

    q_index = lambda b, i, j, qoff: (b, i, 0)
    return (lambda cols: pl.BlockSpec((1, p.block_q, cols), q_index),
            lambda cols: pl.BlockSpec((1, p.block_k, cols), k_index))


def _flash_fwd_bhsd(q, k, v, q_offset, *, scale, causal, blocks, interpret,
                    window=None) -> Tuple[jax.Array, jax.Array]:
    """q,k,v: [bh, s, d]; returns (o [bh, sq, d], lse [bh, sq]). Pads to
    block multiples; padded keys are masked, padded rows cut off."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    p, name = _planned("fwd", q, k, causal, blocks, window)
    q, k, v = _pad_seq(q, p.block_q), _pad_seq(k, p.block_k), \
        _pad_seq(v, p.block_k)
    nq, nk = q.shape[1] // p.block_q, k.shape[1] // p.block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=p.block_q, block_k=p.block_k, q_len=sq, kv_len=sk)
    qspec, kspec = _walks_k(p, causal, nk, window)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[qspec(d), kspec(d), kspec(d)],
            out_specs=[qspec(d), qspec(1)],
            scratch_shapes=[
                pltpu.VMEM((p.block_q, 1), jnp.float32),
                pltpu.VMEM((p.block_q, 1), jnp.float32),
                pltpu.VMEM((p.block_q, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, q.shape[1], 1), jnp.float32),
        ],
        compiler_params=_compiler_params(p),
        interpret=interpret,
        name=name,
    )(q_offset, q, k, v)
    return o[:, :sq], lse[:, :sq, 0]


# ---------------------------------------------------------------- backward

def _dq_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, **tile):
    qi, kk = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(mask_of):
        k = k_ref[0]
        s = _dot(q_ref[0], k, _NT) * scale             # [bq, bk]
        lse = lse_ref[0]                               # [bq, 1]
        p = jnp.exp(s - lse)
        if mask_of:
            # a row no key reaches (or a padded one) has lse = NEG_INF
            p = jnp.where(mask_of(s.shape) & (lse > NEG_INF / 2), p, 0.0)
        dp = _dot(do_ref[0], v_ref[0], _NT)
        ds = p * (dp - delta_ref[0])
        dq_scr[...] += _dot(ds.astype(k.dtype), k, _NN)

    _on_live_steps(step, qi, kk, qoff_ref[0], q_axis=0, **tile)

    @pl.when(kk == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, **tile):
    """Works on the TRANSPOSED tile [bk, bq]: keys down the sublanes, rows
    along the lanes, so that every product is in the MXU's own form (none
    contracts a leading axis, which would transpose a [bq, bk] tile a step)
    and ``lse`` / ``delta`` come lane-dense as [1, bq] rows."""
    kk, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(mask_of):
        q, do = q_ref[0], do_ref[0]
        s = _dot(k_ref[0], q, _NT) * scale             # [bk, bq]
        lse = lse_ref[0]                               # [1, bq]
        p = jnp.exp(s - lse)
        if mask_of:
            p = jnp.where(mask_of(s.shape) & (lse > NEG_INF / 2), p, 0.0)
        dv_scr[...] += _dot(p.astype(do.dtype), do, _NN)
        dp = _dot(v_ref[0], do, _NT)
        ds = p * (dp - delta_ref[0])
        dk_scr[...] += _dot(ds.astype(q.dtype), q, _NN)

    _on_live_steps(step, qi, kk, qoff_ref[0], q_axis=1, **tile)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_bhsd(q, k, v, o, lse, do, q_offset, *, scale, causal, blocks,
                    interpret, window=None):
    """q,k,v,o,do: [bh, s, d]; lse: [bh, sq] -> (dq, dk, dv). Each kernel
    pads to its own blocks: a padded row has ``lse`` = NEG_INF."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    common = dict(scale=scale, causal=causal, window=window, q_len=sq,
                  kv_len=sk)

    def padded(p):
        rows = _round_up(sq, p.block_q) - sq
        return (_pad_seq(q, p.block_q), _pad_seq(k, p.block_k),
                _pad_seq(v, p.block_k), _pad_seq(do, p.block_q),
                jnp.pad(lse, ((0, 0), (0, rows)), constant_values=NEG_INF),
                jnp.pad(delta, ((0, 0), (0, rows))))

    p, name = _planned("dq", q, k, causal, blocks, window)
    qp, kp, vp, dop, lsep, deltap = padded(p)
    nq, nk = qp.shape[1] // p.block_q, kp.shape[1] // p.block_k
    qspec, kspec = _walks_k(p, causal, nk, window)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=p.block_q, block_k=p.block_k,
                          **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[qspec(d), kspec(d), kspec(d), qspec(d), qspec(1),
                      qspec(1)],
            out_specs=[qspec(d)],
            scratch_shapes=[pltpu.VMEM((p.block_q, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(qp.shape, q.dtype)],
        compiler_params=_compiler_params(p),
        interpret=interpret,
        name=name,
    )(q_offset, qp, kp, vp, dop, lsep[..., None], deltap[..., None])[0]

    # dk/dv: grid walks k blocks outer, q blocks inner; before the first
    # live q block (and, with a band, after the last) the q-side index
    # stays on it
    p, name = _planned("dkv", q, k, causal, blocks, window)
    qp, kp, vp, dop, lsep, deltap = padded(p)
    nq, nk = qp.shape[1] // p.block_q, kp.shape[1] // p.block_k

    def q_index(b, j, i, qoff):
        if causal:
            first = _first_live_q(j, qoff[0], p.block_q, p.block_k, nq)
            i = jnp.maximum(i, first)
            if window is not None:
                i = jnp.minimum(i, jnp.maximum(first, _last_live_q(
                    j, qoff[0], p.block_q, p.block_k, nq, window)))
        return i

    kspec = pl.BlockSpec((1, p.block_k, d), lambda b, j, i, qoff: (b, j, 0))
    qspec = pl.BlockSpec((1, p.block_q, d),
                         lambda b, j, i, qoff: (b, q_index(b, j, i, qoff), 0))
    rowspec = pl.BlockSpec((1, 1, p.block_q),
                           lambda b, j, i, qoff: (b, 0, q_index(b, j, i, qoff)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, block_q=p.block_q, block_k=p.block_k,
                          **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk, nq),
            in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
            out_specs=[kspec, kspec],
            scratch_shapes=[pltpu.VMEM((p.block_k, d), jnp.float32),
                            pltpu.VMEM((p.block_k, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(kp.shape, k.dtype),
                   jax.ShapeDtypeStruct(kp.shape, v.dtype)],
        compiler_params=_compiler_params(p),
        interpret=interpret,
        name=name,
    )(q_offset, qp, kp, vp, dop, lsep[:, None], deltap[:, None])
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


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
    return _to_bhsd(q), _to_bhsd(k), _to_bhsd(v)


def _to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b):
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def _explicit(block_q: Optional[int], block_k: Optional[int]
              ) -> Optional[Tuple[int, int]]:
    """The caller's blocks if it gave both, for all three kernels; one
    alone is completed from the other."""
    if block_q is None and block_k is None:
        return None
    return (block_q or block_k, block_k or block_q)


def _qoff(q_offset):
    return jnp.asarray(q_offset, jnp.int32).reshape(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, scale, causal, blocks, interpret, q_offset, window):
    return _flash_core_fwd(q, k, v, scale, causal, blocks, interpret,
                           q_offset, window)[0]


def _flash_core_fwd(q, k, v, scale, causal, blocks, interpret, q_offset,
                    window):
    o, lse = _flash_fwd_bhsd(q, k, v, _qoff(q_offset), scale=scale,
                             causal=causal, blocks=blocks,
                             interpret=interpret, window=window)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(scale, causal, blocks, interpret, q_offset, window, res,
                    do):
    q, k, v, o, lse = res
    return _flash_bwd_bhsd(q, k, v, o, lse, do, _qoff(q_offset), scale=scale,
                           causal=causal, blocks=blocks, interpret=interpret,
                           window=window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    q_offset: int = 0,
                    window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable flash attention over [batch, seq, heads, head_dim].

    Drop-in for ``ray_tpu.ops.attention.mha`` (minus segment_ids/bias — the
    XLA path handles those). ``q_offset``: absolute position of q[0] relative
    to k[0], for decode and ring steps; static here (see
    ``flash_attention_with_lse`` for a traced offset). ``window``: as
    ``mha``'s, a query at ``i`` sees key ``j`` iff ``0 <= i - j < window``
    (causal only). ``block_q`` / ``block_k``: None lets ``plan`` choose per
    kernel.
    """
    _check_window(causal, window)
    b, _, _, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _needs_interpret()
    o = _flash_core(*_prep(q, k, v), scale, causal,
                    _explicit(block_q, block_k), interpret, q_offset, window)
    return _from_bhsd(o, b)


def flash_vjp_chunk(q, k, v, o, do, lse, *,
                    q_offset,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
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
    hkv, sk = k.shape[2], k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _needs_interpret()
    dq, dk, dv = _flash_bwd_bhsd(
        *_prep(q, k, v), _to_bhsd(o), lse.reshape(b * hq, sq), _to_bhsd(do),
        _qoff(q_offset), scale=scale, causal=causal,
        blocks=_explicit(block_q, block_k), interpret=interpret)
    dq, dk, dv = _from_bhsd(dq, b), _from_bhsd(dk, b), _from_bhsd(dv, b)
    if hq != hkv:
        rep = hq // hkv
        dk = dk.reshape(b, sk, hkv, rep, d).sum(axis=3)
        dv = dv.reshape(b, sk, hkv, rep, d).sum(axis=3)
    return dq, dk, dv


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             q_offset=0,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
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
    o, lse = _flash_fwd_bhsd(*_prep(q, k, v), _qoff(q_offset), scale=scale,
                             causal=causal, blocks=_explicit(block_q, block_k),
                             interpret=interpret)
    return _from_bhsd(o, b), lse.reshape(b, hq, sq)
