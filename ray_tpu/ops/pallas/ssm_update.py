"""Mamba-2's one-token update of a decode step, in place on the STACKED
state of every layer and slot: ``ops/pallas/s6_update.py``'s call under this
recurrence's name, with what a head owns spread over its channels.

The state is kept ``[L, slots, n, h p]``, channels minor, as S6's is
(``ops/ssm.py`` says why): a row's ``[128, 4096]`` float32 is 512 whole
vector registers, the decay, ``dt x`` and ``y`` are lane-dense rows
``[1, h p]`` and the sum over ``n`` runs down the sublanes as VPU adds. Kept
``[h, p, n]`` (until PR 62) every head's decay and ``dt x`` was one lane
broadcast across 128, ``y`` one masked lane column and the sum over ``n``
eight cross-lane reductions a head: 5,568 XLU pushes a row, and the call
ran at 72% of what its bytes take at the chip's nominal bandwidth where
this one runs at 80%, the pace the chip gives any stream that reads and
writes (a bare Pallas copy of the rows and XLA's own in-place scaling of
the whole buffer both read 76-78%: PERF.md section 6, PR 62).

A grid step takes ``_ROWS_A_STEP`` rows: one. At 2 MiB a row a step's
transfers are already long beside its bookkeeping: two and four rows a
step took the chip 471.9 and 471.3 us a layer's call where one took 470.0
(PERF.md, PR 62).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas.s6_update import update_in_place

_ROWS_A_STEP = 1
F32 = jnp.float32


def ssm_update_in_place(state: jax.Array, layer, slot0, x: jax.Array,
                        dt: jax.Array, A: jax.Array, B: jax.Array,
                        C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``ops.ssm.ssm_update`` for the rows ``slot0 .. slot0 + b`` of layer
    ``layer`` of ``state`` [L, slots, n, h p] (float32 as served; computed
    in float32 whatever it is kept in), which the caller
    gives up (donated, or a loop's carry): ``x`` [b, h, p], ``dt`` [b, h]
    float32, ``A`` [h], ``B``, ``C`` [b, g, n]. Returns (``y`` [b, h, p] in
    ``x``'s type, ``state`` with those rows stepped). The kernel's name in a
    device trace says what the result's shape does not, the rows it steps
    (``benchmark/kernels/ssm_update.py`` and ``util/hlo_copies.py`` read
    it): ``ssm_update_r<rows>_h<h>_p<p>_n<n>``."""
    n = state.shape[2]
    b, h, p = x.shape

    def per_channel(v):  # a head's value on each of its p lanes
        return jnp.repeat(v.astype(F32), p, axis=-1)

    y, state = update_in_place(
        state, layer, slot0, x.reshape(b, h * p), per_channel(dt),
        per_channel(A)[None], B.astype(F32).swapaxes(1, 2),
        C.astype(F32).swapaxes(1, 2), rows_a_step=_ROWS_A_STEP,
        name=f"ssm_update_r{b}_h{h}_p{p}_n{n}")
    return y.reshape(b, h, p), state
