"""The one-token S6 (Mamba-1) update of a decode step, in place on the
STACKED state of every layer and slot, in one Pallas call:
``ops.ssm.s6_update`` under ``ops/pallas/ssm_update.py``'s contract (the
state crosses HBM once each way, rows outside the launch are not touched,
nothing slices a layer's rows out first).

The state is kept ``[L, slots, n, c]``, channels minor (``ops/ssm.py`` says
why), so a row's state is ``[16, 5120]`` float32 = 80 vector registers' worth
and everything a channel needs (``dt``, ``dt x``, ``y``) is a lane-dense row
``[1, c]``; ``B`` and ``C`` are a column ``[n, 1]`` a row, broadcast along the
lanes; ``A`` ``[n, c]`` is one block for the whole grid. A grid step takes
``block`` rows (eight where the launch is the whole engine, whose first row
is slot 0; one where it is a lone row at any slot): at 327 KB a row a step
of one row would be as long as its own bookkeeping.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash

_VMEM_LIMIT_BYTES = 48 << 20
_ROWS_A_STEP = 8
F32 = jnp.float32


def _kernel(layer, slot0, st_ref, a_ref, dt_ref, xd_ref, b_ref, c_ref,
            out_ref, y_ref, *, block: int):
    del layer, slot0  # the block indices read them
    a = a_ref[...]                                            # [n, c]
    for r in range(block):  # static
        new = (st_ref[r].astype(F32) * jnp.exp(dt_ref[r] * a)
               + xd_ref[r] * b_ref[r])                        # [n, c]
        out_ref[r] = new.astype(out_ref.dtype)
        y_ref[r] = jnp.sum(new * c_ref[r], axis=0, keepdims=True)


def s6_update_in_place(state: jax.Array, layer, slot0, x: jax.Array,
                       dt: jax.Array, A: jax.Array, B: jax.Array,
                       C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``ops.ssm.s6_update`` for the rows ``slot0 .. slot0 + b`` of layer
    ``layer`` of ``state`` [L, slots, n, c] (float32 as served; computed in
    float32 whatever it is kept in), which the caller gives up (donated, or
    a loop's carry): ``x`` [b, c], ``dt`` [b, c] float32, ``A`` [n, c],
    ``B``, ``C`` [b, n]. Returns (``y`` [b, c] in ``x``'s type, ``state``
    with those rows stepped). The kernel's name in a device trace says the
    rows it steps, which its result (the whole stack) does not
    (``benchmark/kernels/s6_update.py`` and ``util/hlo_copies.py`` read it):
    ``s6_update_r<rows>_n<n>_c<c>``."""
    _, slots, n, c = state.shape
    b = x.shape[0]
    # several rows a grid step only where the first is slot 0 (block indices
    # count in blocks): the whole engine
    block = next(r for r in range(min(_ROWS_A_STEP, b), 0, -1)
                 if b % r == 0) if b == slots else 1
    xd = (x.astype(F32) * dt)[:, None, :]                       # [b, 1, c]
    scalars = [jnp.asarray(v, jnp.int32).reshape(1) for v in (layer, slot0)]

    def rows(i, layer, slot0):
        return layer[0], slot0[0] // block + i, 0, 0

    def mine(i, *_):
        return i, 0, 0

    lanes = pl.BlockSpec((block, 1, c), mine)
    column = pl.BlockSpec((block, n, 1), mine)
    stepped = pl.BlockSpec((None, block, n, c), rows)
    state, y = pl.pallas_call(
        lambda *refs: _kernel(*refs, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b // block,),
            in_specs=[stepped, pl.BlockSpec((n, c), lambda i, *_: (0, 0)),
                      lanes, lanes, column, column],
            out_specs=[stepped, lanes]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, 1, c), F32)],
        input_output_aliases={2: 0},  # the state, after the two scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=flash._needs_interpret(),
        name=f"s6_update_r{b}_n{n}_c{c}",
    )(*scalars, state, A.astype(F32), dt[:, None, :], xd,
      B.astype(F32)[:, :, None], C.astype(F32)[:, :, None])
    return y[:, 0].astype(x.dtype), state
