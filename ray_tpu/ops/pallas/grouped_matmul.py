"""Grouped matrix products for the served MoE block: rows sorted by expert,
each expert's rows times that expert's matrix, in one Pallas call.

The rows come in tiles of ``tile`` that belong to one expert each (a
group is padded up to whole tiles by its caller, ``models/moe.py``), and
the weights stay where they lie: the kernel takes the layers' STACKED
``[L, E, k, n]`` array and the layer's index, and its block index picks
``(layer, tile's expert)`` straight out of HBM. Nothing slices a layer's
experts out first (a compiler-made grouped product, ``jax.lax.ragged_dot``,
needs its operand whole, and inside the layer scan that was a copy of all
the layer's experts every step: 2.25 of 3.9 s busy, PR 26's first chip run).
An expert no row was routed to is never read; tiles past the last one in use
point at the block before them, which the pipeline does not fetch again, and
write zeros.

With a second weight array the call is the SwiGLU's first half in one pass:
``silu(rows @ w) * (rows @ w2)``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash

# a weight block [k, cols] is held twice (the pipeline's two buffers), and
# twice again with a second weight: 2 MiB each keeps the call well inside
# the limit below
_WEIGHT_BLOCK_BYTES = 2 << 20
_VMEM_LIMIT_BYTES = 48 << 20


def tile_rows(assignments: int, n_experts: int) -> int:
    """Rows a tile: the power of two at or above the mean rows an expert
    gets, between 16 (a bf16 tile's sublanes) and 256."""
    mean = max(1, -(-assignments // n_experts))
    return min(256, max(16, 1 << (mean - 1).bit_length()))


def n_tiles(tokens: int, top_k: int, n_experts: int, tile: int) -> int:
    """Tiles that hold the ``tokens * top_k`` routed rows in groups padded
    to whole tiles, whatever the routing: at most ``min(E, rows)`` groups
    are not empty and each wastes under one tile; and no expert has more
    rows than there are tokens."""
    rows = tokens * top_k
    touched = min(n_experts, rows)
    return min((rows + touched * (tile - 1)) // tile,
               n_experts * -(-tokens // tile))


def _block_cols(k: int, n: int, itemsize: int) -> int:
    """The widest multiple of 128 that divides ``n`` with a [k, cols] block
    inside the budget; ``n`` whole where no multiple of 128 divides it."""
    if n % 128:
        return n
    fits = [c for c in range(128, n + 1, 128)
            if n % c == 0 and k * c * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits, default=128)


def _kernel(tile_expert, used, layer, x_ref, *refs, swiglu: bool):
    del tile_expert, layer  # the block indices read them
    o_ref = refs[-1]
    live = pl.program_id(0) < used[0]

    @pl.when(live)
    def _():
        x = x_ref[...]

        def times(w_ref):  # a block of the weights, cast here and not whole
            return jnp.dot(x, w_ref[...].astype(x.dtype),
                           preferred_element_type=jnp.float32)

        y = times(refs[0])
        if swiglu:
            y = jax.nn.silu(y) * times(refs[1])
        o_ref[...] = y.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def grouped_matmul(rows: jax.Array, tile_expert: jax.Array, used: jax.Array,
                   layer: jax.Array, w: jax.Array,
                   w2: Optional[jax.Array] = None) -> jax.Array:
    """``rows`` [R, k], in ``len(tile_expert)`` tiles of equal height, times
    ``w[layer, tile_expert[t]]`` for tile ``t``: -> [R, n]. ``w`` (and
    ``w2``) are [L, E, k, n] in their own type (a block is cast to the
    rows' as it is used); ``layer`` and ``used`` (how many tiles hold
    rows; the others give zeros) are int32 [1]. A tile past ``used`` must
    name the last used tile's expert, so that its block is the one already
    there. The kernel's name in a device trace says what the result's
    shape does not (``benchmark/kernels/moe_gmm.py`` reads it):
    ``moe_gmm[_swiglu]_e<experts>_k<k>_t<tile>``."""
    r, k = rows.shape
    n = w.shape[-1]
    tiles = tile_expert.shape[0]
    tile = r // tiles
    cols = _block_cols(k, n, w.dtype.itemsize)
    blocks = n // cols

    def x_index(i, j, tile_expert, used, layer):
        return jnp.minimum(i, used[0] - 1), 0

    def w_index(i, j, tile_expert, used, layer):
        return (layer[0], tile_expert[i], 0,
                jnp.where(i < used[0], j, blocks - 1))

    weights = (w,) if w2 is None else (w, w2)
    return pl.pallas_call(
        functools.partial(_kernel, swiglu=w2 is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tiles, blocks),
            in_specs=[pl.BlockSpec((tile, k), x_index)]
            + [pl.BlockSpec((None, None, k, cols), w_index)] * len(weights),
            out_specs=pl.BlockSpec((tile, cols),
                                   lambda i, j, *_: (i, j))),
        out_shape=jax.ShapeDtypeStruct((r, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=flash._needs_interpret(),
        name=(f"moe_gmm{'_swiglu' if w2 is not None else ''}"
              f"_e{w.shape[1]}_k{k}_t{tile}"),
    )(tile_expert, used, layer, rows, *weights)
