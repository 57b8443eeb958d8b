"""Kimi Delta Attention's recurrence: a delta rule whose forgetting is a
vector a head, in the chunked form that trains, with its backward.

Per head, with a state ``S`` [dk, dv] (keys x values, ``S_0 = 0``), per token
a query ``q`` and a key ``k`` [dk], a value ``v`` [dv], a log-decay ``g``
[dk] (``<= 0``, one a channel) and a step ``beta`` in (0, 1)::

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

(Mamba-2's forgetting, ``ops/ssm.py``, is a scalar a head, and S6's state
has no ``k k^T`` term.) ``kda_recurrent`` is that recurrence as it is
written, a ``lax.scan`` over tokens: what the chunked form is tested
against and nothing a step runs.

The chunked form (``kda_chunked``). Inside a chunk of C tokens, with ``G_r
= sum_{i<=r} g_i`` the log-decay from the chunk's start, the token's
corrected value ``u~_r = beta_r (v_r - S'^T_r k_r)`` solves a unit lower
triangular system: with ``A_ri = beta_r sum_c k_rc k_ic exp(G_rc - G_ic)``
for ``i < r``, ``T = (I + A)^-1 Diag(beta)``, ``W = T (K * exp(G))`` and
``U = T V``, a chunk that starts from the state ``S`` has ``U~ = U - W S``,

    O = (Q * exp(G)) S + tril(Q K^T * decay) U~
    S <- Diag(exp(G_C)) S + (K * exp(G_C - G))^T U~

Three parts (``_chunks``, a ``jax.custom_vjp`` over segments of ``SEGMENT``
chunks). Everything that does not read the state (the two decayed products,
the inverse, ``W``, ``U`` and the reweighted ``Q`` and ``K``) is computed
for a segment's chunks at once (``_insides``). With ``U~`` written out, a
chunk's effect on the state is affine in it, ``S' = Diag(exp(G_C)) S - N S +
B`` with ``N = Kend^T W`` and ``B = Kend^T U`` (``Kend = K * exp(G_C - G)``),
so the walk over the chunks (``_walk_states``, a ``lax.scan``, ``seq / C``
steps in all) carries one ``[dk, dk] x [dk, dv]`` product a head and step
and hands back the state every chunk starts with; a chunk's outputs are
then products over the segment's chunks at once again. JAX differentiates
all of that but the walk, whose backward is the same walk in reverse, one
product a step. The forward keeps its inputs and the state each SEGMENT
starts with (``[segments, b, h, dk, dv]`` float32, named ``kda_states``, and
the output, ``kda_o``: ``RESIDUAL_NAMES``, which ``llama.remat_block``
keeps, so that a rematted backward runs nothing of this a second time
forward). The backward takes the segments in reverse: it rebuilds a segment
from the state it started with, pulls its cotangents back to the inputs and
the state, and only then touches the segment before, so that a segment's
float32 temporaries are live at once and not the sequence's (4 GB of them a
layer at 16,384 tokens and 32 heads, 0.125 GB a segment of 8 chunks). No
state a token is ever held (34 GB a layer) nor one a chunk (0.5 GB), and
nothing loops over tokens.

Decays a channel. ``exp(-G)`` cannot be formed over a chunk: a channel that
forgets fast has ``G`` of minus hundreds. Every exponent here is a
difference ``G_r - G_i`` with ``r`` at or after ``i``, so every factor is in
(0, 1] and one that underflows is a true zero. A chunk is cut into
sub-blocks of ``sub_block`` rows. A (row block, earlier column block) pair
is a product of ``x * exp(G - G_ref)`` and ``k * exp(G_ref - G)`` with
``G_ref`` the row block's first row, which lies between the two; a
diagonal block is summed pair by pair, ``exp(G_r - G_i)`` formed for each
(``_diag_gram``, the only place where the work is not a product: ``C x
sub_block x dk`` multiply-adds a chunk and head).

Precision. Gates, their cumulative sums, the inverse and the state are
float32; a product takes its operands in the inputs' dtype and accumulates
in float32, as the rest of the program does (``W``, ``T`` and the state are
cast on their way into a product; what is added to the state is float32).
The inverse is formed block by block: a 16-row diagonal block by the finite
Neumann product ``(I - D)(I + D^2)(I + D^4)(I + D^8)`` in float32 (XLA's
form) or by forward substitution a column at a time in float32 (the
kernel's, exact), the blocks merged by block forward substitution at
``highest``. Not the whole chunk by that product: the powers of ``A`` grow
binomially before they cancel, by up to C(63, 31) ~ 1e18 over 64 rows where
keys repeat and nothing decays, and by at most C(15, 7) = 6435 over 16, which
float32 carries to 4e-4 in that worst case and the cast of ``T`` to the
products' dtype (2e-3) covers.

What is a kernel's and what is plain XLA: ``impl`` in the plan a step notes
says which (``util/plans.note``). ``"pallas_insides"``: everything of a chunk
that does not read the state (``_insides``: the running sum of the gates,
the two decayed products, the inverse, ``W``, ``U`` and the reweighted ``Q``
and ``K``) is one Pallas call forward and one backward
(``ops/pallas/kda_insides.py``; the running sum never reaches HBM, and the
forward hands the backward the inverse it formed, 4 MiB a segment), where
the shape takes it: the chunk ``CHUNK`` in sub-blocks of ``SUB_BLOCK`` and
both ``d_k`` and ``d_v`` whole lanes of 128. Everywhere else (a chunk shrunk
to a short sequence, a head of no whole lanes, ``impl="xla"`` asked for)
``impl`` is ``"xla"`` and ``_insides`` is written out below, the products
``_decayed_gram`` twice. The kernel's module is imported where a step is
traced with it and nowhere else. The walk over the chunks and a chunk's
outputs (``N``, ``B``, ``_walk_states``, ``kda_out``), which read the state,
and the streams' turn to segments (``_by_segment``) are XLA in both. The
operations carry scopes of their own, names only, for whoever reads a device
trace by hand (they lie under the model's ``kda_scan``): ``kda_insides`` (the
pair's calls, ``kda_insides_fwd_..`` and ``kda_insides_bwd_..``) or, in XLA's
form, ``kda_grams`` (the two decayed products), ``kda_inverse`` and
``kda_reweigh`` (``W``, ``U`` and the reweighted ``Q`` and ``K``); then
``kda_walk`` (``N``, ``B`` and the walk) and ``kda_out``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.util import plans

F32 = jnp.float32

#: tokens a chunk (the published kernels' 64) and rows a sub-block
CHUNK = 64
SUB_BLOCK = 16
#: chunks a segment: what is worked on at once (``_chunks``); a sequence of
#: no whole number of segments is one segment. Chosen on the chip at the
#: benchmark's shape (32 heads x 128, 16,384 tokens; a layer's recurrence,
#: forward and backward, PR 48): 2: 60.8 ms, 4: 59.8, 8: 63.5, 16: 79.6,
#: 32: 102.5, 64: 128.8; 8 and not 4 because a segment keeps the state it
#: starts with (64 MiB a layer at 8, 128 at 4) for under 6% of the call
SEGMENT = 8
#: what ``_chunks``' forward calls the two results its backward and the
#: layer's read (the output, and the states the segments start with): a
#: ``jax.checkpoint`` that keeps these names runs neither the insides nor
#: the walk over chunks again on its way to the backward
RESIDUAL_NAMES = ("kda_o", "kda_states")

#: the most taps a layer's short convolutions may have for
#: ``ops/pallas/kda_mix.py`` to run them: of the rows before a block its
#: kernels read the eight nearest
MAX_CONV_TAPS = 9

_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- the plan

def plan(seq: int, heads: int, d_k: int, d_v: int, batch: int = 1,
         chunk: int = CHUNK, sub_block: int = SUB_BLOCK,
         impl: str = "pallas", conv_taps: int = 4) -> Dict[str, Any]:
    """What ``kda_chunked`` does at one shape; pure. ``chunk`` is shrunk to
    a short sequence (rounded up to whole sub-blocks); a sub-block that
    does not divide the chunk is the chunk. ``boundary_state_bytes``: the
    float32 states the forward keeps for the backward, one a segment.
    ``impl``: ``"pallas_insides"`` where everything of a chunk that does not
    read the state is a kernel pair's (module docstring: what the shape
    takes, and ``impl`` did not ask for ``"xla"``), else ``"xla"``. ``mix``:
    ``"pallas"`` where the layer round the recurrence
    (``models/mixers.kda_half``) runs its elementwise chains, ``conv_taps``
    taps each, as the kernels of ``ops/pallas/kda_mix.py``: both widths whole
    lanes of 128, the taps one halo block (``MAX_CONV_TAPS``) and ``impl``
    not ``"xla"``; else ``"xla"``."""
    sub = min(sub_block, chunk)
    c = min(chunk, -(-max(seq, 1) // sub) * sub)
    if c % sub:
        sub = c
    chunks = -(-seq // c)
    segments = chunks // SEGMENT if chunks % SEGMENT == 0 else 1
    # either kind of kernel: not kept out, and both widths whole lanes
    kernels = impl != "xla" and d_k % 128 == 0 and d_v % 128 == 0
    return {"chunk": c, "sub_block": sub, "chunks": chunks,
            "segments": segments, "heads": heads,
            "d_k": d_k, "d_v": d_v,
            "boundary_state_bytes": segments * batch * heads * d_k * d_v * 4,
            "impl": ("pallas_insides" if kernels and c == CHUNK
                     and sub == SUB_BLOCK else "xla"),
            "mix": ("pallas" if kernels and conv_taps <= MAX_CONV_TAPS
                    else "xla")}


# ------------------------------------------------- inside a chunk, no state

def _masked_decay(G: jax.Array, strict: bool, rows_first: bool = True
                  ) -> jax.Array:
    """``exp(G_i - G_j)`` of G [.., n, d] for every row ``i`` after
    (``strict``) or at or after column ``j``, else 0, laid out [.., i, j, d]
    or (``rows_first`` false) [.., j, i, d]; the mask goes in before the
    ``exp``, so no masked difference is ever exponentiated."""
    n = G.shape[-2]
    first, second = jnp.arange(n)[:, None, None], jnp.arange(n)[None, :, None]
    i, j = (first, second) if rows_first else (second, first)
    Gi, Gj = ((G[..., :, None, :], G[..., None, :, :]) if rows_first
              else (G[..., None, :, :], G[..., :, None, :]))
    return jnp.exp(jnp.where(i > j if strict else i >= j, Gi - Gj, -jnp.inf))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diag_gram(x, k, G, strict):
    """x, k, G [.., n, d] float32 -> [.., n, n]: ``sum_d x_id k_jd
    exp(G_id - G_jd)`` for ``i >= j`` (``strict``: ``i > j``), else 0, each
    pair's decay formed from its own difference. One reduction over
    elementwise work; the backward is two more and never the pairs'
    tensor: the cotangent of ``G`` is ``x * dx - k * dk``, because a pair
    reads ``G`` through ``G_i - G_j`` alone."""
    return jnp.sum(x[..., :, None, :] * k[..., None, :, :]
                   * _masked_decay(G, strict), axis=-1)


def _diag_gram_fwd(x, k, G, strict):
    return _diag_gram(x, k, G, strict), (x, k, G)


def _diag_gram_bwd(strict, res, ct):
    x, k, G = res
    dx = jnp.sum(ct[..., None] * k[..., None, :, :] * _masked_decay(G, strict),
                 axis=-2)
    # the same decays laid out [.., j, i, d], so that each reduction is over
    # its own elementwise work and neither waits for a tensor of pairs
    dk = jnp.sum(jnp.swapaxes(ct, -1, -2)[..., None] * x[..., None, :, :]
                 * _masked_decay(G, strict, rows_first=False), axis=-2)
    return dx, dk, x * dx - k * dk


_diag_gram.defvjp(_diag_gram_fwd, _diag_gram_bwd)


def _decayed_gram(x: jax.Array, k: jax.Array, G: jax.Array, sub: int,
                  strict: bool, cdt) -> jax.Array:
    """x, k, G [.., C, d] float32 -> [.., C, C] float32: ``sum_d x_id k_jd
    exp(G_id - G_jd)`` below the diagonal (``strict``) or on and below it,
    zeros above, in sub-blocks of ``sub`` rows (module docstring). The
    products between sub-blocks take operands in ``cdt``."""
    C, d = x.shape[-2:]
    nb = C // sub
    lead = x.shape[:-2]
    xs, ks, Gs = (a.reshape(*lead, nb, sub, d) for a in (x, k, G))
    ref = Gs[..., 0, :]                                         # [.., nb, d]
    diag = _diag_gram(xs, ks, Gs, strict)                       # [.., nb, sub, sub]
    xg = (xs * jnp.exp(Gs - ref[..., None, :])).astype(cdt)
    rows = []
    for I in range(nb):
        blocks = []
        if I:
            before = I * sub
            kg = (k[..., :before, :] * jnp.exp(
                ref[..., I, None, :] - G[..., :before, :])).astype(cdt)
            blocks.append(jnp.einsum("...id,...jd->...ij", xg[..., I, :, :], kg,
                                     preferred_element_type=F32))
        blocks.append(diag[..., I, :, :])
        if I < nb - 1:
            blocks.append(jnp.zeros((*lead, sub, C - (I + 1) * sub), F32))
        rows.append(jnp.concatenate(blocks, axis=-1))
    return jnp.concatenate(rows, axis=-2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(A: jax.Array, sub: int) -> jax.Array:
    """``(I + A)^-1`` of strictly lower triangular ``A`` [.., C, C] float32
    (what lies on or above the diagonal is not read): each ``sub``-row
    diagonal block as the finite Neumann product (sums in float32, no MXU
    product), the blocks merged by block forward substitution, products at
    ``highest``. Its backward is the inverse's own, ``dA = -X^T
    dX X^T`` below the diagonal: two products, where JAX would walk the
    substitution back row by row through 16 x 16 tiles an eighth full."""
    C = A.shape[-1]
    nb = C // sub
    lead = A.shape[:-2]
    eye = jnp.eye(sub, dtype=F32)
    D = jnp.stack([A[..., I * sub:(I + 1) * sub, I * sub:(I + 1) * sub]
                   for I in range(nb)], axis=-3)                # [.., nb, sub, sub]
    # a diagonal block's inverse is the finite sum of (-D)^k, k < sub, as the
    # product (I - D)(I + D^2)(I + D^4)..; with the blocks along the lanes
    # ([sub, sub, blocks]: a 16 x 16 tile of its own leaves seven lanes in
    # eight empty) each product is one multiply and sum in float32
    def mm(a, b):
        return jnp.sum(a[:, :, None, :] * b[None, :, :, :], axis=1)

    Dt = jnp.moveaxis(D.reshape(-1, sub, sub), 0, -1)
    X, P = eye[:, :, None] - Dt, Dt
    for _ in range(1, (sub - 1).bit_length()):
        P = mm(P, P)
        X = X + mm(X, P)
    X = jnp.moveaxis(X, -1, 0).reshape(D.shape)
    rows = []
    for I in range(nb):
        R = jnp.zeros((*lead, sub, C), F32).at[
            ..., :, I * sub:(I + 1) * sub].set(eye)
        if I:
            R = R - jnp.einsum(
                "...ij,...jk->...ik", A[..., I * sub:(I + 1) * sub, :I * sub],
                jnp.concatenate(rows, axis=-2), precision=_HIGHEST)
        rows.append(jnp.einsum("...ij,...jk->...ik", X[..., I, :, :], R,
                               precision=_HIGHEST))
    return jnp.concatenate(rows, axis=-2)


def _unit_lower_inverse_fwd(A, sub):
    X = _unit_lower_inverse(A, sub)
    return X, X


def _unit_lower_inverse_bwd(sub, X, dX):
    Xt = jnp.swapaxes(X, -1, -2)
    dA = -jnp.einsum("...ij,...jk,...kl->...il", Xt, dX, Xt, precision=_HIGHEST)
    return (jnp.tril(dA, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _insides(q, k, v, g, beta, sub: int, impl: str):
    """What a chunk's step reads that does not depend on the state. q, k
    [b, h, n, C, dk], v [.., dv], g [.., dk] float32, beta [b, h, n, C]
    float32 -> (W [.., C, dk], U [.., C, dv] float32, Aqk [.., C, C], Qg,
    Kend [.., C, dk], gend [.., dk] float32); the operands of the step's
    products in the inputs' dtype. ``impl``: the plan's (module docstring)."""
    cdt = q.dtype
    if impl == "pallas_insides":
        # here and not at the module's top: a process that traces no step
        # with the kernel never loads it
        from ray_tpu.ops.pallas import kda_insides

        with jax.named_scope("kda_insides"):
            return kda_insides.insides(q, k, v, g, beta, sub)
    G = jnp.cumsum(g, axis=-2)
    qf, kf = q.astype(F32), k.astype(F32)
    with jax.named_scope("kda_grams"):
        Akk = _decayed_gram(kf, kf, G, sub, True, cdt)
        Aqk = _decayed_gram(qf, kf, G, sub, False, cdt)
        A, Aqk = beta[..., None] * Akk, Aqk.astype(cdt)
    with jax.named_scope("kda_inverse"):
        T = (_unit_lower_inverse(A, sub) * beta[..., None, :]).astype(cdt)
    with jax.named_scope("kda_reweigh"):
        eG = jnp.exp(G)
        W = jnp.einsum("...ij,...jd->...id", T, (kf * eG).astype(cdt),
                       preferred_element_type=F32).astype(cdt)
        U = jnp.einsum("...ij,...jd->...id", T, v, preferred_element_type=F32)
        Gend = G[..., -1:, :]
        Kend = (kf * jnp.exp(Gend - G)).astype(cdt)
        return W, U, Aqk, (qf * eG).astype(cdt), Kend, jnp.exp(Gend[..., 0, :])


# ------------------------------------------------------- across the chunks
#
# With ``U~ = U - W S`` written out, a chunk's effect on the state is affine
# in it, ``S' = Diag(gend) S - N S + B`` with ``N = Kend^T W`` [dk, dk] and
# ``B = Kend^T U`` [dk, dv], both the insides' alone. So the walk over the
# chunks carries ONE product a step (``_walk_states``), and everything else,
# a chunk's outputs from the state it started with included, is products
# batched over a segment's chunks.

@jax.custom_vjp
def _walk_states(S, gend, N, B):
    """The states the chunks of a segment start with, and the one after the
    last: S [b, h, dk, dv] float32; gend [b, h, n, dk] float32; N
    [b, h, n, dk, dk] in the products' dtype; B [b, h, n, dk, dv] float32
    -> ([b, h, n, dk, dv] float32, [b, h, dk, dv])."""
    return _walk_states_fwd(S, gend, N, B)[0]


def _walk_states_fwd(S, gend, N, B):
    def step(S, xs):
        gend_n, N_n, B_n = xs
        erased = jnp.einsum("bhde,bhev->bhdv", N_n, S.astype(N_n.dtype),
                            preferred_element_type=F32)
        return gend_n[..., None] * S - erased + B_n, S

    S_out, states = jax.lax.scan(step, S, tuple(map(_by_chunk, (gend, N, B))))
    states = jnp.moveaxis(states, 0, 2)
    return (states, S_out), (states, gend, N)


def _walk_states_bwd(res, cts):
    """In reverse, one product a step again: ``lam``, the cotangent of the
    state a chunk ENDS with, gathers the chunk's own (``d_states``) on its
    way back; the cotangents of ``gend``, ``N`` and ``B`` are then products
    over all the chunks at once."""
    states, gend, N = res
    d_states, d_out = cts

    def step(lam, xs):
        gend_n, N_n, d_n = xs
        back = jnp.einsum("bhde,bhdv->bhev", N_n, lam.astype(N_n.dtype),
                          preferred_element_type=F32)
        return d_n + gend_n[..., None] * lam - back, lam

    d_S, lams = jax.lax.scan(step, d_out, tuple(map(
        _by_chunk, (gend, N, d_states))), reverse=True)
    lams = jnp.moveaxis(lams, 0, 2)                      # [b, h, n, dk, dv]
    d_N = -jnp.einsum("bhndv,bhnev->bhnde", lams.astype(N.dtype),
                      states.astype(N.dtype), preferred_element_type=F32)
    return (d_S, jnp.sum(lams * states, axis=-1), d_N.astype(N.dtype), lams)


_walk_states.defvjp(_walk_states_fwd, _walk_states_bwd)


def _segment(S, q, k, v, g, beta, sub: int, impl: str):
    """A segment's chunks from the state ``S`` it starts with: (outputs
    [b, h, n, C, dv] in ``v``'s type, the state it ends with)."""
    cdt = q.dtype
    W, U, Aqk, Qg, Kend, gend = _insides(q, k, v, g, beta, sub, impl)
    with jax.named_scope("kda_walk"):
        N = jnp.einsum("...cd,...ce->...de", Kend, W,
                       preferred_element_type=F32).astype(cdt)
        B = jnp.einsum("...cd,...cv->...dv", Kend, U.astype(cdt),
                       preferred_element_type=F32)
        states, S_out = _walk_states(S, gend, N, B)
    with jax.named_scope("kda_out"):
        Sc = states.astype(cdt)
        Ut = U - jnp.einsum("...cd,...dv->...cv", W, Sc,
                            preferred_element_type=F32)
        O = (jnp.einsum("...cd,...dv->...cv", Qg, Sc,
                        preferred_element_type=F32)
             + jnp.einsum("...ij,...jv->...iv", Aqk, Ut.astype(cdt),
                          preferred_element_type=F32))
    return O.astype(v.dtype), S_out


def _by_chunk(a):
    """[b, h, n, ...] -> [n, b, h, ...]: a scan's leading axis."""
    return jnp.moveaxis(a, 2, 0)


def _by_segment(a, segments: int):
    """[b, h, n, ...] -> [segments, b, h, n / segments, ...]."""
    b, h, n = a.shape[:3]
    return jnp.moveaxis(
        a.reshape(b, h, segments, n // segments, *a.shape[3:]), 2, 0)


def _from_segments(a):
    """``_by_segment``'s inverse."""
    a = jnp.moveaxis(a, 0, 2)
    return a.reshape(*a.shape[:2], -1, *a.shape[4:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _chunks(q, k, v, g, beta, sub, segments, impl):
    """The whole recurrence from a zero state on inputs already cut into
    chunks ([b, h, n, C, ...], ``_insides``' arguments): the outputs
    [b, h, n, C, dv] in ``v``'s type. ``segments`` divides ``n``: a segment
    is worked forward, and (backward) rebuilt from the state it started with
    and its cotangents pulled back, before the next segment is touched, so
    that what is live at once is a segment's and not the sequence's."""
    return _chunks_fwd(q, k, v, g, beta, sub, segments, impl)[0]


def _chunks_fwd(q, k, v, g, beta, sub, segments, impl):
    b, h = q.shape[:2]

    def segment(S, xs):
        O, S_out = _segment(S, *xs, sub, impl)
        return S_out, (O, S)

    _, (O, starts) = jax.lax.scan(
        segment, jnp.zeros((b, h, q.shape[-1], v.shape[-1]), F32),
        tuple(_by_segment(a, segments) for a in (q, k, v, g, beta)))
    O, starts = map(checkpoint_name, (_from_segments(O), starts),
                    RESIDUAL_NAMES)
    return O, (q, k, v, g, beta, starts)


def _chunks_bwd(sub, segments, impl, res, dO):
    *inputs, starts = res

    def segment(dS, xs):
        *mine, S, dO_s = xs
        _, pull = jax.vjp(lambda S, *a: _segment(S, *a, sub, impl), S, *mine)
        dS, *grads = pull((dO_s, dS))
        return dS, tuple(grads)

    _, grads = jax.lax.scan(
        segment, jnp.zeros(starts.shape[1:], F32),
        (*(_by_segment(a, segments) for a in inputs), starts,
         _by_segment(dO, segments)), reverse=True)
    return tuple(map(_from_segments, grads))


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


# ---------------------------------------------------------------- public API

def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, *, chunk: int = CHUNK,
                sub_block: int = SUB_BLOCK, impl: str = "pallas",
                conv_taps: int = 4) -> jax.Array:
    """The recurrence over a sequence from a zero state, in chunks.

    ``q``, ``k`` [b, s, h, dk] (as the model hands them on: normalised, the
    query scaled); ``v`` [b, s, h, dv]; ``g`` [b, s, h, dk] float32, the
    log-decay a channel, ``<= 0``; ``beta`` [b, s, h] float32. Returns ``o``
    [b, s, h, dv] in ``v``'s type. Differentiable in all five.

    Any ``s >= 1``: the sequence is padded at its END to whole chunks with
    ``g = 0`` and ``beta = 0``, under which a token neither decays the state
    nor adds to it. ``impl="xla"`` keeps the kernel out (``plan``); any
    other asks for it where the shape takes it. ``conv_taps`` is the
    caller's, for the plan that is noted alone (``mix``)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    p = plan(s, h, dk, dv, b, chunk, sub_block, impl, conv_taps)
    plans.note("kda", p)
    C, n = p["chunk"], p["chunks"]
    pad = n * C - s

    def chunks(a):  # [b, s, h, ...] -> [b, h, n, C, ...]
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(b, h, n, C, *a.shape[3:])

    o = _chunks(chunks(q), chunks(k), chunks(v), chunks(g.astype(F32)),
                chunks(beta.astype(F32)), p["sub_block"], p["segments"],
                p["impl"])
    return jnp.moveaxis(o.reshape(b, h, n * C, dv), 1, 2)[:, :s]


def kda_recurrent(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array, S0: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence as it is written, a token at a time in float32:
    arguments as ``kda_chunked``'s, ``S0`` [b, h, dk, dv] or None for zeros.
    Returns (``o`` [b, s, h, dv] float32, the state after the last token).
    What the chunked form is tested against; no step runs it."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                 # [b, h, .]
        S = jnp.exp(g_t)[..., None] * S
        u = b_t[..., None] * (v_t - jnp.einsum("bhdv,bhd->bhv", S, k_t,
                                               precision=_HIGHEST))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.einsum("bhdv,bhd->bhv", S, q_t, precision=_HIGHEST)

    S0 = jnp.zeros((b, h, dk, dv), F32) if S0 is None else S0
    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1), S
