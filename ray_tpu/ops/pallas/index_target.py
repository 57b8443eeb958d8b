"""The target of a learned indexer's loss (``ops/sparse_index.py``): the main
attention's weights under the choice, summed over its heads, for a block of
rows, as one TPU Pallas call whose logits never leave VMEM.

With ``q`` [b, rows, h, d] the block's queries, ``k`` [b, hkv, u, d] the keys
its span sees, ``lse`` [b, h, rows] float32 what the attention's forward
returned for those rows and ``select`` [b, rows, u] int8 the choice:

    p[r, u] = select[r, u] * sum_head exp(scale * q[r, head] . k[u, kv(head)]
                                          - lse[head, r])

written once as [b, rows, u] float32. XLA's form of the same lines
(``sparse_index.block_target``) fuses the ``exp`` and the sum over heads
into the product while the keys are few; from some 14,000 keys on it writes
[b, hkv, group, rows, u] float32 to HBM and reads it back for the sum, 537 MB
a block of 256 rows at 32 heads and 16,384 keys where the product is 19
GFLOP (``PERF.md`` section 6, PR 65).

The grid is (batch, key tiles). A step holds the block's queries, a kv head's
``group`` heads laid one after the other as the rows of one matrix [hkv,
group * rows, d], their log-sum-exp as a column beside them, one tile of the
``hkv`` heads' keys and the choice's int8 tile (the tile the flash kernels'
``choice_spec`` reads). For one kv head at a time it multiplies [group *
rows, d] by the tile's [tile, d] on the MXU, accumulating in float32, scales,
takes the log-sum-exp off, exponentiates and sums the group's heads; the
``hkv`` sums are added, masked by the choice and stored. The order is the XLA
form's (products in the operands' dtype, sums float32, the scale after the
product); only the order of the sum over heads differs. A tile wholly after
the block's last row is not multiplied and is written as zeros (the choice is
zero there: a query chooses among its causal past), and fetches no keys and
no choice: its block index stays on the last tile that holds any.

Forward only: the loss holds ``q``, ``k`` and ``lse`` constant and forms its
own gradients (``sparse_index.index_loss``).

A call is named ``index_target_bh<b h>_r<rows>_k<keys>_d<d>_g<group>``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash

#: rows of an int8 tile (the choice's): a block of rows is whole ones
_INT8_ROWS = 32
#: keys a grid step, the largest of these that divides the keys and fits
#: ``flash.py``'s budget of VMEM
TILES = (512, 256, 128)
# [group * rows, tile] float32 temporaries a kv head's pass holds at once
# (the product, its scaled difference, the exponentials), as if none shared
# a buffer
_LOGIT_TEMPS = 3


def vmem_bytes(rows: int, tile: int, heads: int, kv_heads: int, d: int,
               itemsize: int) -> int:
    """What one grid step holds: each operand and result block twice (the
    pipeline's two buffers), the log-sum-exp's [heads * rows, 1] float32
    column padded to 128 lanes, the choice's widening to 32 bits, the sum
    over the kv heads and a kv head's logit-sized temporaries."""
    group = heads // kv_heads
    blocks = 2 * (heads * rows * d * itemsize + heads * rows * flash._LANES * 4
                  + kv_heads * tile * d * itemsize + rows * tile
                  + rows * tile * 4)
    return (blocks + 2 * rows * tile * 4
            + _LOGIT_TEMPS * group * rows * tile * 4)


def _runs(rows: int, tile: int, heads: int, kv_heads: int, d: int,
          itemsize: int) -> bool:
    """May a grid step take ``tile`` keys: ``d`` whole lanes, the rows
    whole int8 tiles, the working set inside the budget."""
    return (tile in TILES and not (d % flash._LANES or rows % _INT8_ROWS
                                   or heads % kv_heads)
            and vmem_bytes(rows, tile, heads, kv_heads, d, itemsize)
            <= flash._VMEM_BUDGET_BYTES)


def tile_keys(rows: int, keys: int, heads: int, kv_heads: int, d: int,
              itemsize: int) -> Optional[int]:
    """The keys a grid step takes at one shape: the largest of ``TILES``
    that divides the keys and may run, None where none does."""
    return next((tile for tile in TILES if keys % tile == 0 and _runs(
        rows, tile, heads, kv_heads, d, itemsize)), None)


def _last_live_tile(first, rows: int, tile: int):
    """The last key tile that holds a key at or before the block's last
    row, ``first + rows - 1``."""
    return (first + rows - 1) // tile


def _kernel(first_ref, q_ref, k_ref, lse_ref, sel_ref, o_ref, *, scale,
            rows: int, tile: int, group: int):
    live = pl.program_id(1) <= _last_live_tile(first_ref[0], rows, tile)

    @pl.when(live)
    def _summed():
        p = jnp.zeros((rows, tile), jnp.float32)
        for kv in range(k_ref.shape[1]):
            logits = flash._dot(q_ref[0, kv], k_ref[0, kv], flash._NT) * scale
            weights = jnp.exp(logits - lse_ref[0, kv])  # [group * rows, tile]
            p = p + weights.reshape(group, rows, tile).sum(0)
        o_ref[0] = jnp.where(sel_ref[0].astype(jnp.int32) != 0, p, 0.0)

    @pl.when(jnp.logical_not(live))
    def _unseen():
        o_ref[...] = jnp.zeros_like(o_ref)


def index_target(q: jax.Array, k: jax.Array, lse: jax.Array,
                 select: jax.Array, first, *, scale: float, tile: int
                 ) -> jax.Array:
    """``p`` [b, rows, u] float32 (module docstring) of ``q`` [b, rows, h,
    d], ``k`` [b, hkv, u, d], ``lse`` [b, h, rows] float32, ``select`` [b,
    rows, u] int8 and ``first``, the position of the block's first row (an
    int32 scalar, traced or not). ``tile``: ``tile_keys``'s."""
    b, rows, h, d = q.shape
    hkv, u = k.shape[1:3]
    group = h // hkv
    if u % tile or not _runs(rows, tile, h, hkv, d, q.dtype.itemsize):
        raise ValueError(
            f"index_target of q{list(q.shape)} k{list(k.shape)} with tile "
            f"{tile}: the head width is whole lanes of {flash._LANES}, the "
            f"rows whole tiles of {_INT8_ROWS}, the keys whole tiles of one "
            f"of {TILES}")
    if select.shape != (b, rows, u) or select.dtype != jnp.int8:
        raise ValueError(f"select is {select.dtype}{list(select.shape)}; a "
                         f"choice is int8 [{b}, {rows}, {u}]")
    # a kv head's ``group`` heads as the rows of one matrix, their
    # log-sum-exp the column beside it (head = kv * group + g)
    q_rows = jnp.moveaxis(q, 2, 1).reshape(b, hkv, group * rows, d)
    lse_col = lse.astype(jnp.float32).reshape(b, hkv, group * rows, 1)
    first = jnp.asarray(first, jnp.int32).reshape(1)

    def on_tile(j, first):
        return jnp.minimum(j, _last_live_tile(first[0], rows, tile))

    def whole(cols):  # the block's heads, every grid step of a batch row
        return pl.BlockSpec((1, hkv, group * rows, cols),
                            lambda i, j, first: (i, 0, 0, 0))

    need = vmem_bytes(rows, tile, h, hkv, d, q.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, rows=rows, tile=tile,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, u // tile),
            in_specs=[
                whole(d),
                pl.BlockSpec((1, hkv, tile, d), lambda i, j, first:
                             (i, 0, on_tile(j, first), 0)),
                whole(1),
                pl.BlockSpec((1, rows, tile), lambda i, j, first:
                             (i, 0, on_tile(j, first)))],
            out_specs=pl.BlockSpec((1, rows, tile),
                                   lambda i, j, first: (i, 0, j))),
        out_shape=jax.ShapeDtypeStruct((b, rows, u), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(flash._VMEM_DEFAULT_LIMIT_BYTES, need)),
        interpret=flash._needs_interpret(),
        name=f"index_target_bh{b * h}_r{rows}_k{u}_d{d}_g{group}",
    )(first, q_rows, k, lse_col, select)
