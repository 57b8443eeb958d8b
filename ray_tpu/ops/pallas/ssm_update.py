"""The one-token state-space update of a decode step, in place on the
STACKED state of every layer and slot, in one Pallas call.

``ops.ssm.ssm_update`` written in XLA compiled, for the TPU, to two fusions
that each read the rows' state (one reduces it against ``C``, the other
writes it back): 3.0 x the rows' bytes a step where 2.0 is the least
(PERF.md, PR 31). Here a grid step holds one row's state ``[h, p, n]`` in
VMEM, computes ``new = state * decay + (dt x) (x) B`` and ``y = new C`` from
it and writes ``new`` back over what it read: the state crosses HBM once
each way. The kernel takes the whole ``[L, slots, h, p, n]`` buffer with its
output aliased to it, and its block index picks ``(layer, slot0 + row)``
straight out of HBM, as ``grouped_matmul`` picks its expert: nothing slices
a layer's rows out first, and rows outside the launch are not touched.

What a head needs along the state's ``p`` (sublane) axis, ``dt x`` and ``y``,
travels as ``[rows, p, h]`` so that a head's column is a lane slice; ``B``
and ``C`` lie along ``n`` (lanes) as they come.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash

_VMEM_LIMIT_BYTES = 48 << 20
F32 = jnp.float32


def _kernel(layer, slot0, st_ref, decay_ref, xd_ref, b_ref, c_ref,
            out_ref, y_ref, *, heads: int, per_group: int):
    del layer, slot0  # the block indices read them
    decay, xd = decay_ref[...], xd_ref[...]          # [1, h], [p, h]
    for i in range(heads):  # static: a head's column is a lane slice
        g = i // per_group
        new = (st_ref[i].astype(F32) * decay[:, i:i + 1]
               + xd[:, i:i + 1] * b_ref[g:g + 1, :])  # [p, n]
        out_ref[i] = new.astype(out_ref.dtype)
        y_ref[:, i:i + 1] = jnp.sum(new * c_ref[g:g + 1, :], axis=-1,
                                    keepdims=True)


def ssm_update_in_place(state: jax.Array, layer, slot0, x: jax.Array,
                        dt: jax.Array, A: jax.Array, B: jax.Array,
                        C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``ops.ssm.ssm_update`` for the rows ``slot0 .. slot0 + b`` of layer
    ``layer`` of ``state`` [L, slots, h, p, n] (float32 as served; computed
    in float32 whatever it is kept in), which the caller
    gives up (donated, or a loop's carry): ``x`` [b, h, p], ``dt`` [b, h]
    float32, ``A`` [h], ``B``, ``C`` [b, g, n]. Returns (``y`` [b, h, p] in
    ``x``'s type, ``state`` with those rows stepped). The kernel's name in a
    device trace says what the result's shape does not, the rows it steps
    (``benchmark/kernels/ssm_update.py`` and ``util/hlo_copies.py`` read
    it): ``ssm_update_r<rows>_h<h>_p<p>_n<n>``."""
    _, _, h, p, n = state.shape
    b, g = x.shape[0], B.shape[1]
    decay = jnp.exp(dt * A)[:, None, :]                          # [b, 1, h]
    xd = (x.astype(F32) * dt[..., None]).swapaxes(1, 2)          # [b, p, h]
    scalars = [jnp.asarray(v, jnp.int32).reshape(1) for v in (layer, slot0)]

    def row(i, layer, slot0):
        return layer[0], slot0[0] + i, 0, 0, 0

    def mine(i, *_):
        return i, 0, 0

    per_row = [pl.BlockSpec((None, 1, h), mine), pl.BlockSpec((None, p, h), mine),
               pl.BlockSpec((None, g, n), mine), pl.BlockSpec((None, g, n), mine)]
    rows = pl.BlockSpec((None, None, h, p, n), row)
    state, y = pl.pallas_call(
        lambda *refs: _kernel(*refs, heads=h, per_group=h // g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,), in_specs=[rows] + per_row,
            out_specs=[rows, pl.BlockSpec((None, p, h), mine)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, p, h), F32)],
        input_output_aliases={2: 0},  # the state, after the two scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=flash._needs_interpret(),
        name=f"ssm_update_r{b}_h{h}_p{p}_n{n}",
    )(*scalars, state, decay, xd, B.astype(F32), C.astype(F32))
    return y.swapaxes(1, 2).astype(x.dtype), state
