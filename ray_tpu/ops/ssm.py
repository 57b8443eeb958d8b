"""Two state-space recurrences, Mamba-2's (below) and Mamba-1's (S6, at the
end of this file), each twice: over a whole sequence in chunks
(what a prefill runs) and for one token (what a decode step runs).

Per head, with a state ``h`` [P, N] (channel, state), a scalar decay
``A < 0`` and per token a step ``dt > 0``, an input ``x`` [P] and the two
projections ``B``, ``C`` [N] that a group of heads shares::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t

``ssd_scan`` is the chunked form of it (Dao & Gu 2024, "state space
duality"): inside a chunk of Q tokens the outputs are one masked product
``(C B^T * decay)(dt x)``, which the MXU runs; across chunks the recurrence
runs on each chunk's summed state, Q times fewer steps. It starts from any
state and hands back the one after the last token, which is what a prefill
leaves in a slot for the decode steps to go on from. ``ssm_update`` is the
recurrence itself for one token a row.

Both are plain XLA. The scan is what a prefill runs. The one-token update
here is the recurrence as it is written and what the kernel is tested
against: the engine's decode step runs ``ops/pallas/ssm_update.py`` instead
(the chip's finding, PERF.md PR 31: XLA compiled this form to two passes
over the rows' state). Both take and hand back the state as it is STORED,
``[b, n, h p]``: state element major, channels minor, head ``i``'s channels
the lanes ``i p .. (i + 1) p``. It is the layout S6's state has, for the
reason given at the end of this file: with the channels on the lanes what a
channel owns (its decay, ``dt x``, ``y``) is a lane-dense row and the sum
over ``n`` an add down the sublanes, where ``[h, p, n]`` made every head's
decay a lane broadcast and the sum a cross-lane reduction (PERF.md, PR 62).
The state is float32 whatever the activations are: a decode step
adds ``dt x B`` of the order of 1e-3 of the state to a state that decays by
as little a step, and bf16's eight bits lose it. The decays, their
cumulative sums and the products that touch the state are float32 too; the
products inside a chunk take the activations' type and accumulate in
float32. The skip term ``D x`` and the gate are the model's (they are not
part of the recurrence).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def n_chunks(seq: int, chunk: int) -> int:
    """Chunks ``ssd_scan`` cuts a ``seq``-token sequence into."""
    return -(-seq // min(chunk, seq))


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int, h0: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a sequence, in chunks.

    ``x`` [b, s, h, p]; ``dt`` [b, s, h] float32, already positive; ``A``
    [h] float32, negative; ``B``, ``C`` [b, s, g, n] with ``h % g == 0``
    (head ``i`` reads group ``i // (h / g)``); ``h0`` [b, n, h p] float32,
    the stored layout, or None for zeros. Returns (``y`` [b, s, h, p] in
    ``x``'s type, the state after token ``s - 1`` [b, n, h p] float32).
    Inside, the chunks' states are ``[.., p, n]`` (the products that form
    and read them contract and keep ``n`` as the MXU takes it: formed
    ``[.., n, p]`` the scan took 1.8 x the time on the chip, PERF.md PR 62),
    so ``h0`` is turned once on its way in and the last state once on its
    way out: a row's 2 MiB a layer, once a prefill.

    Any ``s >= 1``: the chunk is ``min(chunk, s)`` and the sequence is
    padded at its END to whole chunks with ``dt = 0``, under which a token
    neither decays the state nor adds to it, so the state handed back is the
    one after the last real token."""
    b, s, h, p = x.shape
    g, n = B.shape[-2:]
    r = h // g
    q = min(chunk, s)
    c = n_chunks(s, chunk)
    pad = c * q - s
    if pad:
        x, dt, B, C = (jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
                       for a in (x, dt, B, C))
    cdt = x.dtype
    # [b, c, q, ...] with the heads as (group, head of the group)
    xd = (x.astype(F32) * dt[..., None]).astype(cdt).reshape(b, c, q, g, r, p)
    a = (dt * A).reshape(b, c, q, g, r)                     # log decay a token
    acs = jnp.cumsum(a, axis=2)                             # ... up to token i
    Bc, Cc = B.reshape(b, c, q, g, n), C.reshape(b, c, q, g, n)

    # inside a chunk: token i reads token j <= i through exp(acs_i - acs_j)
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                        preferred_element_type=F32)
    seg = acs[:, :, :, None] - acs[:, :, None, :]           # [b,c,i,j,g,r]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    mixed = (scores.transpose(0, 1, 3, 4, 2)[..., None] * decay).astype(cdt)
    y = jnp.einsum("bcijgr,bcjgrp->bcigrp", mixed, xd,
                   preferred_element_type=F32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(acs[:, :, -1:] - acs)                  # [b,c,q,g,r]
    summed = jnp.einsum("bcjgr,bcjgrp,bcjgn->bcgrpn", to_end, xd.astype(F32),
                        Bc.astype(F32))
    whole = jnp.exp(acs[:, :, -1])                          # [b,c,g,r]

    # across chunks: the state entering each chunk, and the last one
    state0 = (jnp.zeros((b, g, r, p, n), F32) if h0 is None
              else h0.astype(F32).reshape(b, n, g, r, p).transpose(
                  0, 2, 3, 4, 1))

    def step(state, per_chunk):
        w, add = per_chunk
        return state * w[..., None, None] + add, state

    last, entering = jax.lax.scan(
        step, state0, (whole.swapaxes(0, 1), summed.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1)                      # [b,c,g,r,p,n]
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", Cc.astype(F32), entering
                       ) * jnp.exp(acs)[..., None]
    y = y.reshape(b, c * q, h, p)[:, :s].astype(cdt)
    return y, last.transpose(0, 4, 1, 2, 3).reshape(b, n, h * p)


def ssm_update(state: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
               B: jax.Array, C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The recurrence for one token a row: ``state`` [b, n, h p] float32 (the
    stored layout), ``x`` [b, h, p], ``dt`` [b, h] float32 and positive,
    ``A`` [h], ``B``, ``C`` [b, g, n]. Returns (``y`` [b, h, p] in ``x``'s
    type, the new state). Elementwise and a sum over ``n``: one pass over
    the state."""
    b, h, p = x.shape
    g = B.shape[1]

    def per_channel(v):  # [b, h] -> [b, 1, h p], a head's value on its lanes
        return jnp.repeat(v, p, axis=-1)[:, None, :]

    def per_group(m):    # [b, g, n] -> [b, n, h p], a group's on its heads'
        return jnp.repeat(m.astype(F32).swapaxes(1, 2), h // g * p, axis=-1)

    new = (state.astype(F32) * per_channel(jnp.exp(dt * A))
           + (x.astype(F32) * dt[..., None]).reshape(b, 1, h * p)
           * per_group(B))
    y = (new * per_group(C)).sum(1)
    return y.reshape(b, h, p).astype(x.dtype), new


def causal_conv(xs: jax.Array, tail: jax.Array, w: jax.Array, bias: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution over the last ``K`` positions of every
    channel, with bias: ``xs`` [b, s, ch], ``tail`` [b, K - 1, ch] the
    inputs before ``xs`` (zeros at a sequence's start), ``w`` [K, ch],
    ``bias`` [ch]. Returns (the convolution [b, s, ch], before its
    activation, and the new tail: the last ``K - 1`` inputs)."""
    k, s = w.shape[0], xs.shape[1]
    window = jnp.concatenate([tail.astype(xs.dtype), xs], axis=1)
    out = sum(window[:, j:j + s] * w[j] for j in range(k)) + bias
    return out, window[:, s:]


# ---- S6, the selective recurrence of Mamba-1 ----------------------------------
#
# Per channel ``c`` of ``d_inner`` and state element ``n``, with ``A`` [n, c]
# negative, a step ``dt`` [c] positive, an input ``x`` [c] and the
# projections ``B``, ``C`` [n] that ALL channels share::
#
#     h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
#     y_t[c]    = sum_n h_t[n, c] C_t[n]
#
# Every element of the state has a decay of its own, so nothing here is a
# matrix product (Mamba-2's one decay a head is what makes ``ssd_scan``'s
# chunks products): the scan is the recurrence itself over time, elementwise
# on a state kept ``[n, c]``, channels minor: 5120 channels are 40 x 128
# lanes and 16 states two sublane tiles, where the published ``[c, n]`` would
# leave 112 of 128 lanes empty. State, decays and ``dt`` float32, as above.


def s6_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, h0: Optional[jax.Array] = None, *, unroll: int = 8
            ) -> Tuple[jax.Array, jax.Array]:
    """The S6 recurrence over a sequence: ``x`` [b, s, c]; ``dt`` [b, s, c]
    float32, positive; ``A`` [n, c] float32, negative; ``B``, ``C``
    [b, s, n]; ``h0`` [b, n, c] float32 or None for zeros. Returns (``y``
    [b, s, c] in ``x``'s type, the state after token ``s - 1`` [b, n, c]
    float32). A ``lax.scan`` over time, ``unroll`` steps a trip."""
    b, s, c = x.shape
    n = A.shape[0]
    xd = x.astype(F32) * dt
    state0 = jnp.zeros((b, n, c), F32) if h0 is None else h0.astype(F32)

    def step(state, t):
        dt_t, xd_t, b_t, c_t = t                  # [b, c] [b, c] [b, n] [b, n]
        state = (state * jnp.exp(dt_t[:, None, :] * A)
                 + xd_t[:, None, :] * b_t.astype(F32)[:, :, None])
        return state, (state * c_t.astype(F32)[:, :, None]).sum(1)

    last, y = jax.lax.scan(
        step, state0, tuple(a.swapaxes(0, 1) for a in (dt, xd, B, C)),
        unroll=min(unroll, s))
    return y.swapaxes(0, 1).astype(x.dtype), last


def s6_update(state: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
              B: jax.Array, C: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The S6 recurrence for one token a row: ``state`` [b, n, c] float32,
    ``x`` [b, c], ``dt`` [b, c] float32 and positive, ``A`` [n, c], ``B``,
    ``C`` [b, n]. Returns (``y`` [b, c] in ``x``'s type, the new state).
    The recurrence as it is written, and what ``ops/pallas/s6_update.py``
    (the engine's decode step) is tested against."""
    new = (state.astype(F32) * jnp.exp(dt[:, None, :] * A)
           + (x.astype(F32) * dt)[:, None, :] * B.astype(F32)[:, :, None])
    y = (new * C.astype(F32)[:, :, None]).sum(1)
    return y.astype(x.dtype), new
