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
float32 scratch, the [block_q, block_k] float32 temporaries of the clear
body, a sub-tile's mask) is inside ``_VMEM_BUDGET_BYTES``, so a wide head
with float32 operands shrinks it; a sequence shorter than the tile is one
block of its own (padded) length. The call raises Mosaic's scoped VMEM limit
to what the plan counts. An explicit ``block_q=`` / ``block_k=`` wins for
all three kernels (on the chip such a block is a multiple of 128 rows or
the whole padded sequence).

What is skipped. With the causal mask, a (q block, k block) pair above the
diagonal — by the RUNTIME offset — does nothing: no products, no ``exp``, and
no fetch, because the block index of the walked operand is clamped to the
last (in dkv: first) live block and the pipeline does not fetch an index
again. A pair wholly below the diagonal and inside both true lengths skips
the mask arithmetic. A pair that an edge crosses (the diagonal, the band's
lower edge, a true length) is cut into ``_SUB_BLOCK`` sub-tiles and each is
judged as the pair was, by the same runtime offset: one with no live
element does nothing, one with no masked element runs the clear body on its
slices of the refs, and only one the edge goes through builds a mask (the
iotas' difference held against two scalars; the terms for padded keys and
rows only where the call has any). The sub-tile follows the tile: a side it
does not divide, or a shorter one, is not cut, so small explicit blocks are
masked whole as before. Rows no key reaches give ``o`` = 0, ``lse`` =
NEG_INF.

How the sub-tile was chosen (PR 44's chip runs, each call alone, bh 48,
s 8192, d 128, bf16; a full causal call walks 8 crossed pairs of 36, one
with ``window`` 4096 walks 12 of 30). Cutting costs ~0.7 us for each
sub-tile entered (its four or five products and the elementwise passes
between them run one after another, where the whole tile's overlap), so
smaller is not better: dkv takes 10.47 / 10.02 ms (full / banded) uncut,
9.99 / 9.00 at 512 x 512 (three of four sub-tiles worked, two masked),
10.79 / 9.87 at 256 x 256 (ten of sixteen, four masked), 12.90 / 13.06 at
128 x 128; dq 9.11 / 8.58, 8.77 / 7.82, 9.29 / 8.38, 11.98 / 12.49. The
forward, whose masked tile costs 1.5 clear ones and not 2, loses at every
size (7.48 / 6.71 uncut, 7.95 / 7.26 at 512, 9.60 / 9.74 at 256: each
sub-tile rescales its rows' accumulator), so its sub-tile is its tile. An
unrolled double loop over the sub-tiles times the same as the loop over
their keys (within 0.7%) and takes 1.5 s to trace and lower where this
takes 0.33 (the parent 0.22). The cheaper mask is worth 0.14 / 0.18 /
0.36 ms (fwd / dq / dkv) of a banded call and nothing of a full one.

The band. With ``window`` w (causal only) a query at position p sees the keys
in (p - w, p]. A pair wholly below the band is skipped like one above the
diagonal, from the other side: the walked index is clamped to the first (in
dkv: last) live block too, and the band's lower edge builds the mask where it
crosses a block. ``window=None`` is the causal kernel as it was.

The choice. ``select=`` [b, s_q, s_k] int8 is a mask that is data and not
arithmetic on positions: a learned indexer's pick of the keys a query sees
(``ops/sparse_index.py``), one for all heads. All three kernels take it as
one more operand, streamed tile by tile beside K and V: the tile of the
step's (q block, k block), its batch row that of the step's head, fetched by
the index the walked operand has (a pair the causal test skips fetches no
tile of it either); dkv, which works on the transposed tile, reads the
choice turned once, [b, s_k, s_q], by one XLA transpose a call. A tile's
int8 is widened to 32 bits and compared with 0, and the mask it gives is
ANDed with the causal test where the diagonal crosses the pair and applied
alone where it does not: under a choice no pair is clear, every live one
runs the masked body (``plan`` counts a tile's 2 + 4 bytes an element).
Nothing of the square is skipped for the choice's sake: a tile no row chose
from is walked and masked, so the kernels' work is the causal walk's
whatever the choice keeps (at s 16,384 and 2,048 keys a query, 23% of the
pairs: what skipping by a tile's emptiness, or gathering, would be worth is
``benchmark/kernels/flash_select.py``'s roofline). The forward hands back
its log-sum-exp beside ``o`` (``flash_attention_chosen``), which the
indexer's loss rebuilds the softmax's weights from. A call under a choice
ends its name ``_t<topk>``, the most keys a row chose, ties aside; a call
without one is named, planned and traced as it was.

Names. A call is named ``flash_<kind>_bh<bh>_q<sq>_k<sk>_d<d>_c<causal>_w<w>``
(w 0: no band), the true lengths before padding: a device trace shows a
``pallas_call`` under its name, and a call's result does not say what of
the square it skipped. Where the values are not as wide as the queries and
keys the width reads ``_d<d>v<dv>_`` (next paragraph); a call of one width
keeps the name above to the letter.

Two widths. ``v`` (and so ``o``, ``do``, ``dv``) may have a head width
``dv`` of its own, as latent attention's uncompressed form has (queries and
keys 192 = 128 + 64, values 128): ``QK^T`` and the products that give ``dq``
and ``dk`` contract or produce ``d`` columns, ``PV``, ``dP = dO V^T`` and
``dV`` ``dv``. Nothing is padded to the wider of the two: every block's last
dimension is the array's own, which Mosaic takes whole whatever its size. A
width that is no multiple of the 128 lanes (192) lies in VMEM as two lane
tiles, the second half empty, and a product that contracts it runs the MXU's
128-deep pass twice, the second half idle: ``QK^T`` at 192 costs what it
would at 256, and so ``plan`` counts it (``_vmem_bytes`` rounds each width
up to whole lanes).

Precision. Products take their operands in the inputs' dtype and accumulate
in float32, forward and backward alike (``p`` and ``ds`` are cast as the
forward casts ``p``); the softmax statistics are float32. float32 inputs
multiply in float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.util import plans

KINDS = ("fwd", "dq", "dkv")
# what ``_flash_core_fwd`` calls its output and log-sum-exp
# (``jax.ad_checkpoint.checkpoint_name``), the two results the backward
# kernels read: a ``jax.checkpoint`` whose policy keeps these names does not
# run the forward kernel again in its backward; to any other they are the
# identity
RESIDUAL_NAMES = ("flash_o", "flash_lse")

_LANES = 128
# rows a side of the tile a kernel takes where the sequence and the budget
# allow it: the fastest of 256 ... 4096 for each of the three (module docstring)
_TARGET_BLOCK = 1024
# what a tile's working set may take of VMEM (v5e and v6e hold 128 MiB, v7x
# 64), and Mosaic's scoped default, which a call raises only if it must
_VMEM_BUDGET_BYTES = 40 << 20
_VMEM_DEFAULT_LIMIT_BYTES = 16 << 20
# rows, keys a side of the sub-tile a pair that an edge crosses is worked in,
# the fastest of 128 ... 1024 for each of the three (module docstring); a
# tile they do not divide is its own sub-tile, as the forward's is
_SUB_BLOCK = {"fwd": (1024, 1024), "dq": (512, 512), "dkv": (512, 512)}
# [block_q, block_k] float32 temporaries a kernel's clear body holds at once
# (scores, weights, their casts; the backward adds dp, ds), as if none shared
# a buffer: Mosaic fits the planned tile into half. The masked body works on
# a sub-tile, where it holds the mask's two iotas beside them
_SCORE_TEMPS = {"fwd": 3, "dq": 5, "dkv": 5}
_MASK_TEMPS = 2


def _needs_interpret() -> bool:
    """Interpret mode is for the CPU tests and nothing else: every other
    backend compiles the kernel, or fails where it cannot."""
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------- the plan

class Plan(NamedTuple):
    """One kernel's tiling at one shape. ``grid_steps``, ``live_steps`` and
    ``edge_steps`` count (q block, k block) pairs of one head: all of them,
    those that do work at ``q_offset`` 0, and of those the ones an edge (the
    diagonal, the band's lower edge, a true length) crosses, which are
    worked ``sub_block`` (rows, keys) at a time."""
    kind: str
    block_q: int
    block_k: int
    grid_steps: int
    live_steps: int
    edge_steps: int
    sub_block: Tuple[int, int]
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


def _sub_block(kind: str, block_q: int, block_k: int) -> Tuple[int, int]:
    """The sub-tile of a (block_q, block_k) tile: it follows the tile, a
    side that the sub-tile's does not divide (a shorter one) is not cut."""
    sub_q, sub_k = _SUB_BLOCK[kind]
    return (block_q if block_q % sub_q else sub_q,
            block_k if block_k % sub_k else sub_k)


def _vmem_bytes(kind: str, block_q: int, block_k: int, head_dim: int,
                itemsize: int, value_dim: Optional[int] = None,
                select: bool = False) -> int:
    """What one grid step holds: each operand and result block twice (the
    pipeline's two buffers), a [rows, 1] float32 block padded to 128 lanes,
    the float32 scratch, the score-sized temporaries of the clear body and
    the masked body's, a sub-tile's. ``value_dim``: the width of ``v``,
    ``o``, ``do`` and ``dv`` where it is not ``head_dim``. ``select``: the
    call takes a choice (module docstring), an int8 tile twice and its
    widening to 32 bits, a score-sized temporary more."""
    d = _round_up(head_dim, _LANES)
    e = _round_up(value_dim or head_dim, _LANES)
    q_blk, k_blk = block_q * d * itemsize, block_k * d * itemsize
    o_blk, v_blk = block_q * e * itemsize, block_k * e * itemsize
    q_col = block_q * _LANES * 4
    if kind == "fwd":    # q k v -> o lse | m l acc
        blocks = 2 * (q_blk + o_blk + k_blk + v_blk + q_col)
        scratch = 2 * q_col + block_q * e * 4
    elif kind == "dq":   # q k v do lse delta -> dq | dq
        blocks = 2 * (2 * q_blk + o_blk + k_blk + v_blk + 2 * q_col)
        scratch = block_q * d * 4
    else:                # q k v do lse delta (rows) -> dk dv | dk dv
        blocks = 2 * (q_blk + o_blk + 2 * k_blk + 2 * v_blk
                      + 2 * 8 * block_q * 4)
        scratch = block_k * (d + e) * 4
    sub_q, sub_k = _sub_block(kind, block_q, block_k)
    chosen = block_q * block_k * (2 + 4) if select else 0
    return (blocks + scratch + _SCORE_TEMPS[kind] * block_q * block_k * 4
            + _MASK_TEMPS * sub_q * sub_k * 4 + chosen)


def _live(q_lo, rows: int, k_lo, keys: int):
    """Does any of the ``rows`` rows from position ``q_lo`` see any of the
    ``keys`` keys from ``k_lo`` under the causal mask: the last row is at
    or after the first key."""
    return k_lo <= q_lo + rows - 1


def _in_band(q_lo, rows: int, k_lo, keys: int, window: int):
    """Does any of the keys lie in the band of any of the rows: the first
    row is less than ``window`` after the last key."""
    return q_lo - (k_lo + keys - 1) < window


def _live_and_clear(row0, rows: int, key0, keys: int, q_offset, *, causal,
                    window, q_len: int, kv_len: int):
    """Of the rectangle of ``rows`` rows from index ``row0`` (position
    ``row0 + q_offset``) and ``keys`` keys from ``key0``: does it hold a
    pair the mask leaves alive, and does it hold no other (every key at or
    before every row and inside every row's band, no padded row or key).
    The one rule for a (q block, k block) pair and for a sub-tile of it,
    on Python integers (``plan``) and on the kernel's scalars alike."""
    q_lo = row0 + q_offset
    live = (row0 < q_len) & (key0 < kv_len)
    clear = (row0 + rows <= q_len) & (key0 + keys <= kv_len)
    if causal:
        live = live & _live(q_lo, rows, key0, keys)
        clear = clear & (key0 + keys - 1 <= q_lo)
    if window is not None:
        live = live & _in_band(q_lo, rows, key0, keys, window)
        clear = clear & (q_lo + rows - 1 - key0 < window)
    return live, clear


def _check_window(causal: bool, window: Optional[int]) -> None:
    if window is not None and (not causal or window < 1):
        raise ValueError(f"window {window!r} needs causal attention and at "
                         f"least one key a query")


def plan(seq_q: int, seq_k: int, head_dim: int, itemsize: int, causal: bool,
         kind: str, blocks: Optional[Tuple[int, int]] = None,
         window: Optional[int] = None,
         value_dim: Optional[int] = None, select: bool = False) -> Plan:
    """The tiling of one kernel (``kind`` of ``KINDS``) at one shape; pure.
    Explicit ``blocks`` (block_q, block_k) are kept, shrunk to a short
    sequence. ``window`` moves no block's size: it only takes the pairs
    below the band out of ``live_steps``. ``value_dim``: the values' head
    width where it is not ``head_dim`` (None or equal: the plan of one
    width, as it was). ``select``: the call takes a choice, whose tile
    counts in the working set and which leaves no pair clear: every live
    step is an edge step."""
    if kind not in KINDS:
        raise ValueError(f"kind {kind!r} is not one of {KINDS}")
    _check_window(causal, window)
    if blocks is not None:
        bq, bk = _pick_block(blocks[0], seq_q), _pick_block(blocks[1], seq_k)
    else:
        tq = tk = _TARGET_BLOCK
        # halve the longer side until the tile fits
        while (_vmem_bytes(kind, tq, tk, head_dim, itemsize, value_dim, select)
               > _VMEM_BUDGET_BYTES and max(tq, tk) > _LANES):
            tq, tk = (tq // 2, tk) if tq >= tk else (tq, tk // 2)
        bq, bk = _fit_block(tq, seq_q), _fit_block(tk, seq_k)
    nq, nk = -(-seq_q // bq), -(-seq_k // bk)
    pairs = [_live_and_clear(i * bq, bq, j * bk, bk, 0, causal=causal,
                             window=window, q_len=seq_q, kv_len=seq_k)
             for i in range(nq) for j in range(nk)]
    need = _vmem_bytes(kind, bq, bk, head_dim, itemsize, value_dim, select)
    return Plan(kind, bq, bk, nq * nk, sum(live for live, _ in pairs),
                sum(live and not (clear and not select)
                    for live, clear in pairs),
                _sub_block(kind, bq, bk), need,
                max(_VMEM_DEFAULT_LIMIT_BYTES, need))


def _planned(kind: str, q, k, v, causal: bool,
             blocks: Optional[Tuple[int, int]],
             window: Optional[int],
             topk: Optional[int] = None) -> Tuple[Plan, str]:
    """The plan of the kernel about to be built on q, k [bh, s, d] and v
    [bh, s, dv], noted, and the call's name (module docstring). ``topk``:
    the call takes a choice of at most that many keys a query."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    two = dv != d
    p = plan(sq, sk, d, q.dtype.itemsize, causal, kind, blocks, window,
             dv if two else None, topk is not None)
    plans.note("flash", {
        **p._asdict(), "seq_q": sq, "seq_k": sk, "head_dim": d,
        "itemsize": q.dtype.itemsize, "causal": causal, "window": window,
        **({"value_dim": dv} if two else {}),
        **({} if topk is None else {"topk": topk})})
    width = f"d{d}v{dv}" if two else f"d{d}"
    return p, (f"flash_{kind}_bh{bh}_q{sq}_k{sk}_{width}_c{int(causal)}"
               f"_w{window or 0}" + ("" if topk is None else f"_t{topk}"))


# ------------------------------------------------------- what a step skips

def _on_live_steps(step, qi, kk, qoff, *, causal, window, block_q, block_k,
                   sub_block, q_len, kv_len, q_axis, chosen=None):
    """Run ``step(rows, keys, mask_of)`` over the (q block, k block) pair,
    ``rows`` and ``keys`` slices of the tile. Not at all if the mask leaves
    the pair nothing; once over the whole tile with ``mask_of`` None if no
    element of it is masked (``_live_and_clear``). A pair an edge crosses
    is cut into ``sub_block`` sub-tiles and each is judged by the same rule
    from the same runtime offset: nothing, the clear body, or, where the
    edge goes through it, the body with the function that builds the mask
    of that sub-tile: keys inside the true length and, causal, at or before
    their row and less than ``window`` before it. The sub-tiles' rows are
    static slices (in dkv they index lanes), their keys a loop's: two
    bodies a row of sub-tiles are traced and lowered, not two a sub-tile.
    A tile that is its own sub-tile is masked whole. ``q_axis`` is the axis
    rows lie on (1 in dkv's transposed tile). ``chosen(rows, keys)``, where
    the call takes a choice: the tile's part of it as a mask, which every
    body then applies, the clear one alone and the crossed one beside the
    positions' own."""
    sub_q, sub_k = sub_block

    def run(row0, rows, key0, keys, cut):
        q0, k0 = qi * block_q + row0, kk * block_k + key0
        live, clear = _live_and_clear(q0, rows, k0, keys, qoff, causal=causal,
                                      window=window, q_len=q_len,
                                      kv_len=kv_len)
        at = pl.ds(row0, rows), pl.ds(key0, keys)

        def mask_of(shape, lse=None):
            # element (i, j) is row q0 + qoff + i against key k0 + j: the
            # iotas' difference is held against scalars, and a term that no
            # tile of this call can need (no padded key, no padded row) is
            # not built
            key = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
            mask = None
            if causal:
                ahead = jax.lax.broadcasted_iota(
                    jnp.int32, shape, q_axis) - key
                lead = q0 + qoff - k0
                mask = ahead >= -lead
                if window is not None:
                    mask = mask & (ahead < window - lead)
            if mask is None or kv_len % block_k:
                inside = key < kv_len - k0
                mask = inside if mask is None else mask & inside
            if lse is not None and q_len % block_q:
                # a padded row has lse = NEG_INF (one no key reaches too,
                # but the terms above leave such a row nothing already)
                mask = mask & (lse > NEG_INF / 2)
            return mask

        if chosen is None:
            on_clear, on_crossed = None, mask_of
        else:
            def on_clear(shape, lse=None):
                return chosen(*at)

            def on_crossed(shape, lse=None):
                return mask_of(shape, lse) & chosen(*at)

        pl.when(live & clear)(lambda: step(*at, on_clear))

        @pl.when(live & jnp.logical_not(clear))
        def _crossed():
            if not cut:
                step(*at, on_crossed)
                return
            for r in range(0, rows, sub_q):
                pl.loop(0, keys // sub_k)(lambda c: run(
                    r, sub_q, pl.multiple_of(c * sub_k, sub_k), sub_k,
                    cut=False))

    run(0, block_q, 0, block_k, cut=(sub_q, sub_k) != (block_q, block_k))


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


def _chosen(sel_ref, q_axis: int):
    """``_on_live_steps``'s ``chosen`` for a kernel whose choice tile is
    ``sel_ref`` [1, rows, keys] int8 (dkv's transposed, [1, keys, rows]:
    ``q_axis`` 1); nothing for a kernel without one."""
    if sel_ref is None:
        return {}

    def chosen(rows, keys):
        at = (rows, keys) if q_axis == 0 else (keys, rows)
        return sel_ref[(0, *at)].astype(jnp.int32) != 0

    return {"chosen": chosen}


def _takes_choice(kernel, n_in: int):
    """``kernel`` with one more input after its ``n_in`` (the scalar
    prefetch not counted), the choice's tile, handed on as ``sel_ref``."""
    def with_choice(qoff_ref, *refs, **static):
        return kernel(qoff_ref, *refs[:n_in], *refs[n_in + 1:],
                      sel_ref=refs[n_in], **static)

    return with_choice


_NT = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]
_NN = (((1,), (0,)), ((), ()))  # [m, n] x [n, d] -> [m, d]


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- forward

def _fwd_kernel(qoff_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                 # outputs
                m_scr, l_scr, acc_scr,          # scratch
                *, scale, sel_ref=None, **tile):
    qi, kk = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(rows, keys, mask_of):
        s = _dot(q_ref[0, rows], k_ref[0, keys], _NT) * scale  # [rows, keys]
        if mask_of:
            s = jnp.where(mask_of(s.shape), s, NEG_INF)
        m_prev = m_scr[rows]                           # [rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # A row no key has reached yet keeps m at NEG_INF, where
        # exp(s - m) = exp(0) would count its masked keys: exponentiate
        # against 0 there, so that p = exp(NEG_INF) = 0 and alpha = 0.
        m_exp = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new) if mask_of \
            else m_new
        alpha = jnp.exp(m_prev - m_exp)
        p = jnp.exp(s - m_exp)                         # [rows, keys] fp32
        l_scr[rows] = l_scr[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[rows] = m_new
        acc_scr[rows] = acc_scr[rows] * alpha + _dot(
            p.astype(v_ref.dtype), v_ref[0, keys], _NN)

    _on_live_steps(step, qi, kk, qoff_ref[0], q_axis=0,
                   **_chosen(sel_ref, 0), **tile)

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
    the k-side block, which past the last live one (and, with a band,
    before the first) stays where it is, and a choice's tile of the two."""
    def k_index(b, i, j, qoff):
        if causal:
            last = _last_live_k(i, qoff[0], p.block_q, p.block_k, nk)
            j = jnp.minimum(j, last)
            if window is not None:
                j = jnp.maximum(j, jnp.minimum(last, _first_live_k(
                    i, qoff[0], p.block_q, p.block_k, window)))
        return b, j, 0

    q_index = lambda b, i, j, qoff: (b, i, 0)

    def choice_spec(heads):
        # a choice [b, sq, sk]: the tile of the step's q block and of the k
        # block the walk is on (an index that stays on a live block fetches
        # no tile of the choice either), the batch row of the step's head
        return pl.BlockSpec(
            (1, p.block_q, p.block_k),
            lambda b, i, j, qoff: (b // heads, i, k_index(b, i, j, qoff)[1]))

    return (lambda cols: pl.BlockSpec((1, p.block_q, cols), q_index),
            lambda cols: pl.BlockSpec((1, p.block_k, cols), k_index),
            choice_spec)


def _pad_choice(select, rows: int, cols: int):
    """``select`` [b, r, c] padded with zeros (nothing chosen) to whole
    blocks of ``rows`` x ``cols``."""
    _, r, c = select.shape
    pad_r, pad_c = (-r) % rows, (-c) % cols
    if pad_r or pad_c:
        select = jnp.pad(select, ((0, 0), (0, pad_r), (0, pad_c)))
    return select


def _flash_fwd_bhsd(q, k, v, q_offset, *, scale, causal, blocks, interpret,
                    window=None, select=None, topk=None
                    ) -> Tuple[jax.Array, jax.Array]:
    """q,k: [bh, s, d], v: [bh, s, dv]; returns (o [bh, sq, dv], lse
    [bh, sq]). Pads to block multiples; padded keys are masked, padded rows
    cut off. ``select`` [b, sq, sk] int8: the choice (module docstring), of
    at most ``topk`` keys a query."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    p, name = _planned("fwd", q, k, v, causal, blocks, window, topk)
    q, k, v = _pad_seq(q, p.block_q), _pad_seq(k, p.block_k), \
        _pad_seq(v, p.block_k)
    nq, nk = q.shape[1] // p.block_q, k.shape[1] // p.block_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=p.block_q, block_k=p.block_k, sub_block=p.sub_block, q_len=sq,
        kv_len=sk)
    qspec, kspec, choice_spec = _walks_k(p, causal, nk, window)
    choice, operands = [], (q_offset, q, k, v)
    if select is not None:
        kernel = _takes_choice(kernel, 3)
        choice = [choice_spec(bh // select.shape[0])]
        operands += (_pad_choice(select, p.block_q, p.block_k),)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[qspec(d), kspec(d), kspec(dv), *choice],
            out_specs=[qspec(dv), qspec(1)],
            scratch_shapes=[
                pltpu.VMEM((p.block_q, 1), jnp.float32),
                pltpu.VMEM((p.block_q, 1), jnp.float32),
                pltpu.VMEM((p.block_q, dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, q.shape[1], dv), q.dtype),
            jax.ShapeDtypeStruct((bh, q.shape[1], 1), jnp.float32),
        ],
        compiler_params=_compiler_params(p),
        interpret=interpret,
        name=name,
    )(*operands)
    return o[:, :sq], lse[:, :sq, 0]


# ---------------------------------------------------------------- backward

def _dq_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, scale, sel_ref=None, **tile):
    qi, kk = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(rows, keys, mask_of):
        k = k_ref[0, keys]
        s = _dot(q_ref[0, rows], k, _NT) * scale       # [rows, keys]
        lse = lse_ref[0, rows]                         # [rows, 1]
        p = jnp.exp(s - lse)
        if mask_of:
            p = jnp.where(mask_of(s.shape, lse), p, 0.0)
        dp = _dot(do_ref[0, rows], v_ref[0, keys], _NT)
        ds = p * (dp - delta_ref[0, rows])
        dq_scr[rows] += _dot(ds.astype(k.dtype), k, _NN)

    _on_live_steps(step, qi, kk, qoff_ref[0], q_axis=0,
                   **_chosen(sel_ref, 0), **tile)

    @pl.when(kk == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(qoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, sel_ref=None,
                **tile):
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

    def step(rows, keys, mask_of):
        q, do = q_ref[0, rows], do_ref[0, rows]
        s = _dot(k_ref[0, keys], q, _NT) * scale       # [keys, rows]
        lse = lse_ref[0, :, rows]                      # [1, rows]
        p = jnp.exp(s - lse)
        if mask_of:
            p = jnp.where(mask_of(s.shape, lse), p, 0.0)
        dv_scr[keys] += _dot(p.astype(do.dtype), do, _NN)
        dp = _dot(v_ref[0, keys], do, _NT)
        ds = p * (dp - delta_ref[0, :, rows])
        dk_scr[keys] += _dot(ds.astype(q.dtype), q, _NN)

    _on_live_steps(step, qi, kk, qoff_ref[0], q_axis=1,
                   **_chosen(sel_ref, 1), **tile)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_bhsd(q, k, v, o, lse, do, q_offset, *, scale, causal, blocks,
                    interpret, window=None, select=None, topk=None):
    """q,k: [bh, s, d]; v,o,do: [bh, s, dv]; lse: [bh, sq] -> (dq, dk,
    dv). Each kernel pads to its own blocks: a padded row has ``lse`` =
    NEG_INF. ``select``, ``topk``: as the forward's; dkv, which works on
    the transposed tile, reads the choice turned once, [b, sk, sq]."""
    bh, sq, d = q.shape
    sk, dv = k.shape[1], v.shape[2]
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    common = dict(scale=scale, causal=causal, window=window, q_len=sq,
                  kv_len=sk)

    def padded(p):
        rows = _round_up(sq, p.block_q) - sq
        return (_pad_seq(q, p.block_q), _pad_seq(k, p.block_k),
                _pad_seq(v, p.block_k), _pad_seq(do, p.block_q),
                jnp.pad(lse, ((0, 0), (0, rows)), constant_values=NEG_INF),
                jnp.pad(delta, ((0, 0), (0, rows))))

    p, name = _planned("dq", q, k, v, causal, blocks, window, topk)
    qp, kp, vp, dop, lsep, deltap = padded(p)
    nq, nk = qp.shape[1] // p.block_q, kp.shape[1] // p.block_k
    qspec, kspec, choice_spec = _walks_k(p, causal, nk, window)
    kernel = functools.partial(_dq_kernel, block_q=p.block_q,
                               block_k=p.block_k, sub_block=p.sub_block,
                               **common)
    choice, chosen = [], ()
    if select is not None:
        kernel = _takes_choice(kernel, 6)
        choice = [choice_spec(bh // select.shape[0])]
        chosen = (_pad_choice(select, p.block_q, p.block_k),)
    dq = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[qspec(d), kspec(d), kspec(dv), qspec(dv), qspec(1),
                      qspec(1), *choice],
            out_specs=[qspec(d)],
            scratch_shapes=[pltpu.VMEM((p.block_q, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(qp.shape, q.dtype)],
        compiler_params=_compiler_params(p),
        interpret=interpret,
        name=name,
    )(q_offset, qp, kp, vp, dop, lsep[..., None], deltap[..., None],
      *chosen)[0]

    # dk/dv: grid walks k blocks outer, q blocks inner; before the first
    # live q block (and, with a band, after the last) the q-side index
    # stays on it
    p, name = _planned("dkv", q, k, v, causal, blocks, window, topk)
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

    def kspec(cols):
        return pl.BlockSpec((1, p.block_k, cols),
                            lambda b, j, i, qoff: (b, j, 0))

    def qspec(cols):
        return pl.BlockSpec(
            (1, p.block_q, cols),
            lambda b, j, i, qoff: (b, q_index(b, j, i, qoff), 0))

    rowspec = pl.BlockSpec((1, 1, p.block_q),
                           lambda b, j, i, qoff: (b, 0, q_index(b, j, i, qoff)))
    kernel = functools.partial(_dkv_kernel, block_q=p.block_q,
                               block_k=p.block_k, sub_block=p.sub_block,
                               **common)
    choice, chosen = [], ()
    if select is not None:
        heads = bh // select.shape[0]
        kernel = _takes_choice(kernel, 6)
        choice = [pl.BlockSpec(
            (1, p.block_k, p.block_q),
            lambda b, j, i, qoff: (b // heads, j, q_index(b, j, i, qoff)))]
        chosen = (_pad_choice(select.swapaxes(1, 2), p.block_k, p.block_q),)
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk, nq),
            in_specs=[qspec(d), kspec(d), kspec(dv), qspec(dv), rowspec,
                      rowspec, *choice],
            out_specs=[kspec(d), kspec(dv)],
            scratch_shapes=[pltpu.VMEM((p.block_k, d), jnp.float32),
                            pltpu.VMEM((p.block_k, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(kp.shape, k.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)],
        compiler_params=_compiler_params(p),
        interpret=interpret,
        name=name,
    )(q_offset, qp, kp, vp, dop, lsep[:, None], deltap[:, None], *chosen)
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
    o, lse = map(checkpoint_name, (o, lse), RESIDUAL_NAMES)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(scale, causal, blocks, interpret, q_offset, window, res,
                    do):
    q, k, v, o, lse = res
    return _flash_bwd_bhsd(q, k, v, o, lse, do, _qoff(q_offset), scale=scale,
                           causal=causal, blocks=blocks, interpret=interpret,
                           window=window)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_chosen(q, k, v, select, scale, causal, blocks, interpret, topk):
    """The kernels under a choice: (o, lse). ``lse`` comes out for a reader
    that wants the softmax's weights again (``ops/sparse_index.py``) and
    takes no cotangent; nor does the choice."""
    return _flash_chosen_fwd(q, k, v, select, scale, causal, blocks,
                             interpret, topk)[0]


def _flash_chosen_fwd(q, k, v, select, scale, causal, blocks, interpret,
                      topk):
    o, lse = _flash_fwd_bhsd(q, k, v, _qoff(0), scale=scale, causal=causal,
                             blocks=blocks, interpret=interpret,
                             select=select, topk=topk)
    o, lse = map(checkpoint_name, (o, lse), RESIDUAL_NAMES)
    return (o, lse), (q, k, v, select, o, lse)


def _flash_chosen_bwd(scale, causal, blocks, interpret, topk, res, ct):
    q, k, v, select, o, lse = res
    do, _ = ct
    return (*_flash_bwd_bhsd(q, k, v, o, lse, do, _qoff(0), scale=scale,
                             causal=causal, blocks=blocks,
                             interpret=interpret, select=select, topk=topk),
            None)


_flash_chosen.defvjp(_flash_chosen_fwd, _flash_chosen_bwd)


def flash_attention_chosen(q: jax.Array, k: jax.Array, v: jax.Array,
                           select: jax.Array, *, topk: int,
                           causal: bool = True,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """``flash_attention(..., select=)`` with the log-sum-exp beside the
    output: (o [b, s, h, dv], lse [b, h, s] float32, no gradient through
    it). ``select`` [b, s_q, s_k] int8, one choice for all heads: a query
    sees the keys whose entry is not 0 (and, ``causal``, that lie at or
    before it); a row that chose nothing gives ``o`` 0, ``lse`` NEG_INF.
    ``topk`` is the most keys a row chose, ties aside, which the calls
    carry in their names (``_t<topk>``) and nothing computes from."""
    b, sq, hq, d = q.shape
    if select.shape != (b, sq, k.shape[1]) or select.dtype != jnp.int8:
        raise ValueError(
            f"select is {select.dtype}{list(select.shape)}; a choice is "
            f"int8 [batch, s_q, s_k] = {[b, sq, k.shape[1]]}")
    scale = scale if scale is not None else d ** -0.5
    if interpret is None:
        interpret = _needs_interpret()
    o, lse = _flash_chosen(*_prep(q, k, v), select, scale, causal,
                           _explicit(block_q, block_k), interpret, int(topk))
    return _from_bhsd(o, b), jax.lax.stop_gradient(lse).reshape(b, hq, sq)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    q_offset: int = 0,
                    window: Optional[int] = None,
                    select: Optional[jax.Array] = None,
                    topk: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable flash attention over [batch, seq, heads, head_dim];
    ``v`` may have a head width of its own, which is the output's.

    Drop-in for ``ray_tpu.ops.attention.mha`` (minus segment_ids/bias — the
    XLA path handles those). ``q_offset``: absolute position of q[0] relative
    to k[0], for decode and ring steps; static here (see
    ``flash_attention_with_lse`` for a traced offset). ``window``: as
    ``mha``'s, a query at ``i`` sees key ``j`` iff ``0 <= i - j < window``
    (causal only). ``select``, ``topk``: a choice of keys a query, as
    ``flash_attention_chosen`` takes it (no band and no offset beside it).
    ``block_q`` / ``block_k``: None lets ``plan`` choose per kernel.
    """
    _check_window(causal, window)
    if select is not None:
        if window is not None or q_offset or topk is None:
            raise ValueError("a choice needs topk and takes neither a "
                             "window nor a q_offset")
        return flash_attention_chosen(
            q, k, v, select, topk=topk, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret)[0]
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
    softmax weight. q,k: [b,s,h,d]; v,o,do: [b,s,h,dv]; lse: [b,h,s];
    q_offset may be traced.
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
        dv = dv.reshape(b, sk, hkv, rep, v.shape[-1]).sum(axis=3)
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
