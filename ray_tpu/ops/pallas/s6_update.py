"""The one-token state-space update of a decode step, in place on the
STACKED state of every layer and slot, in one Pallas call: S6's
(``ops.ssm.s6_update``) and, through ``ops/pallas/ssm_update.py``, Mamba-2's
(``ops.ssm.ssm_update``). One body, because one recurrence: ``new = state *
exp(dt A) + dt x B``, ``y = sum_n new C``, where S6 has a decay an element of
the state (``A`` [n, c]) and Mamba-2 one a channel (``A`` a row [1, c], a
head's value on its ``p`` lanes); the body sees which by ``A``'s shape.

``ops.ssm.*_update`` written in XLA compiled, for the TPU, to two fusions
that each read the rows' state (one reduces it against ``C``, the other
writes it back): 3.0 x the rows' bytes a step where 2.0 is the least
(PERF.md, PR 31). Here a grid step holds its rows' state in VMEM, computes
``new`` and ``y`` from it and writes ``new`` back over what it read: the
state crosses HBM once each way. The kernel takes the whole
``[L, slots, n, c]`` buffer with its output aliased to it, and its block
index picks ``(layer, slot0 + row)`` straight out of HBM, as
``grouped_matmul`` picks its expert: nothing slices a layer's rows out
first, and rows outside the launch are not touched.

The state is kept ``[L, slots, n, c]``, channels minor (``ops/ssm.py`` says
why), so a row's state is whole vector registers (S6: ``[16, 5120]`` float32
= 80; Mamba-2: ``[128, 4096]`` = 512) and everything a channel needs
(``dt``, ``dt x``, ``y``) is a lane-dense row ``[1, c]``; ``B`` and ``C`` are
a column ``[n, 1]`` a row and group of channels, broadcast along the lanes
(a group's channels are one run of ``c / g`` lanes), and the sum over ``n``
runs down the sublanes as VPU adds; ``A`` is one block for the whole grid. A
grid step takes ``block`` rows (up to the caller's ``rows_a_step`` where the
launch is the whole engine, whose first row is slot 0; one where it is a
lone row at any slot): S6's eight, since at 327 KB a row a step of one row
would be as long as its own bookkeeping.
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
    groups = b_ref.shape[-1]
    width = st_ref.shape[-1] // groups
    for g in range(groups):  # static: a group's channels are a run of lanes
        lanes, col = slice(g * width, (g + 1) * width), slice(g, g + 1)
        a = a_ref[:, lanes]                          # [n, c / g], or [1, c / g]
        for r in range(block):  # static
            new = (st_ref[r, :, lanes].astype(F32)
                   * jnp.exp(dt_ref[r, :, lanes] * a)
                   + xd_ref[r, :, lanes] * b_ref[r, :, col])  # [n, c / g]
            out_ref[r, :, lanes] = new.astype(out_ref.dtype)
            y_ref[r, :, lanes] = jnp.sum(new * c_ref[r, :, col], axis=0,
                                         keepdims=True)


def update_in_place(state: jax.Array, layer, slot0, x: jax.Array,
                    dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
                    *, rows_a_step: int, name: str
                    ) -> Tuple[jax.Array, jax.Array]:
    """The call itself, for either recurrence: ``state`` [L, slots, n, c],
    which the caller gives up; ``x`` [b, c]; ``dt`` [b, c] float32; ``A``
    [n, c] or [1, c] float32; ``B``, ``C`` [b, n, g] float32, group ``j``
    the channels ``j c / g .. (j + 1) c / g``. Returns (``y`` [b, c] in
    ``x``'s type, ``state`` with the rows ``slot0 .. slot0 + b`` of layer
    ``layer`` stepped); ``name`` is the call's in a device trace."""
    _, slots, n, c = state.shape
    b, g = x.shape[0], B.shape[-1]
    # several rows a grid step only where the first is slot 0 (block indices
    # count in blocks): the whole engine
    block = next(r for r in range(min(rows_a_step, b), 0, -1)
                 if b % r == 0) if b == slots else 1
    xd = (x.astype(F32) * dt)[:, None, :]                       # [b, 1, c]
    scalars = [jnp.asarray(v, jnp.int32).reshape(1) for v in (layer, slot0)]

    def rows(i, layer, slot0):
        return layer[0], slot0[0] // block + i, 0, 0

    def mine(i, *_):
        return i, 0, 0

    lanes = pl.BlockSpec((block, 1, c), mine)
    column = pl.BlockSpec((block, n, g), mine)
    stepped = pl.BlockSpec((None, block, n, c), rows)
    state, y = pl.pallas_call(
        lambda *refs: _kernel(*refs, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b // block,),
            in_specs=[stepped, pl.BlockSpec(A.shape, lambda i, *_: (0, 0)),
                      lanes, lanes, column, column],
            out_specs=[stepped, lanes]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((b, 1, c), F32)],
        input_output_aliases={2: 0},  # the state, after the two scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=flash._needs_interpret(),
        name=name,
    )(*scalars, state, A, dt[:, None, :], xd, B, C)
    return y[:, 0].astype(x.dtype), state


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
    n, c = state.shape[2:]
    return update_in_place(
        state, layer, slot0, x, dt, A.astype(F32), B.astype(F32)[:, :, None],
        C.astype(F32)[:, :, None], rows_a_step=_ROWS_A_STEP,
        name=f"s6_update_r{x.shape[0]}_n{n}_c{c}")
