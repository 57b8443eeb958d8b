"""One new position a row written into keys and values that are kept a
head's positions together (``[L, slots, heads, length, head_dim]``:
``models/sambay.py``), in place, in one Pallas call for both buffers.

Positions are the sublane axis of such a buffer, so one position is a sixteenth
of a tile. Written as an XLA scatter, the chip's compiler first re-laid the
WHOLE buffer positions-before-heads (where a position's heads are a padded
tile of their own) and back: four copies of the rings a launch and four of the
shared buffer a step (PERF.md, PR 34). Here a grid step takes one row's tile
of ``tile`` positions round the new one out of HBM (the block index reads the
position prefetched), puts the new position in with a select and writes the
tile back over what it read: 2 x ``heads x tile x head_dim`` a row and buffer,
and rows outside the launch, other layers and every other position are not
touched.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash


def _kernel(layer, slot0, at, *refs, tile: int, length: int, n: int):
    del layer, slot0  # the block indices read them
    p = at[pl.program_id(0)]
    for buf, new, out in zip(refs[:n], refs[n:2 * n], refs[2 * n:]):
        block = buf[...]                                   # [h, tile, d]
        here = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1) == p % tile
        out[...] = jnp.where(here & (p >= 0) & (p < length),
                             new[...].astype(block.dtype), block)


def kv_write_in_place(bufs: Sequence[jax.Array], layer, slot0, at: jax.Array,
                      new: Sequence[jax.Array]) -> Tuple[jax.Array, ...]:
    """``buf[layer, slot0 + r, :, at[r], :] = new[r]`` for every row ``r``
    and every ``buf`` [L, slots, h, length, d] of ``bufs`` with its ``new``
    [b, h, d]; a row whose ``at`` lies outside ``0 .. length - 1`` writes
    nothing. The caller gives the buffers up (donated, or a loop's carry).
    The kernel's name says what its results' shapes do not, the rows and
    the tile it moves (``util/hlo_copies.py`` and
    ``benchmark/kernels/kv_write.py`` read it):
    ``kv_write_r<rows>_h<h>_t<tile>_d<d>``."""
    _, _, h, length, d = bufs[0].shape
    b, n = at.shape[0], len(bufs)
    tile = next((t for t in (16, 8) if length % t == 0), length)
    scalars = [jnp.asarray(v, jnp.int32).reshape(1) for v in (layer, slot0)]
    at = at.astype(jnp.int32)

    def tile_of(i, layer, slot0, at):
        return (layer[0], slot0[0] + i, 0,
                jnp.clip(at[i], 0, length - 1) // tile, 0)

    def mine(i, *_):
        return i, 0, 0, 0

    tiles = pl.BlockSpec((None, None, h, tile, d), tile_of)
    out = pl.pallas_call(
        lambda *refs: _kernel(*refs, tile=tile, length=length, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b,),
            in_specs=[tiles] * n + [pl.BlockSpec((None, h, 1, d), mine)] * n,
            out_specs=[tiles] * n),
        out_shape=[jax.ShapeDtypeStruct(buf.shape, buf.dtype) for buf in bufs],
        input_output_aliases={3 + j: j for j in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=flash._needs_interpret(),
        name=f"kv_write_r{b}_h{h}_t{tile}_d{d}",
    )(*scalars, at, *bufs, *(x[:, :, None, :] for x in new))
    return tuple(out)
