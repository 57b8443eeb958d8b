"""A learned choice of the positions a query attends to (DeepSeek Sparse
Attention's lightning indexer, as Keye-VL-2.0's ``sa_config`` sizes it): the
indexer's scores, the exact ``topk``-th largest of each row's causal past,
the choice as a mask, and the loss that trains the indexer, each a block of
rows at a time so that nothing ``[heads, s, s]`` exists.

With ``qI`` [b, s, J, e] the indexer's queries, ``kI`` [b, s, e] its one
key a position and ``w`` [b, s, J] float32 a weight a query head (the
scale ``J ** -0.5 * e ** -0.5`` folded in by the caller):

- ``I[t, u] = sum_j w[t, j] * relu(qI[t, j] . kI[u])``: products in the
  operands' dtype, sums float32 (``block_scores``);
- ``tau[t]`` = the ``topk``-th largest of ``I[t, 0..t]``, exactly, ``-inf``
  while the row has no more than ``topk`` entries (``threshold``): float32
  scores map to integers in the same order, and the integer with ``topk``
  entries at or above it is built ``_BITS`` bits a pass over the block from
  the top, ``32 / _BITS`` passes of ``2 ** _BITS - 1`` counts (a sort of
  16,384 entries a row is two orders of magnitude more passes);
- ``S_t = {u <= t : I[t, u] >= tau[t]}``, ties at ``tau`` all kept
  (``choose``: the int8 mask [b, s, s] the flash kernels take as
  ``select=``, and three counts);
- ``L_I = mean_t KL(p[t, .] || softmax_{u in S_t} I[t, u])``, ``p`` the main
  attention's weights summed over its heads and L1-normalised over ``S_t``,
  rebuilt from its ``q``, ``k`` and the log-sum-exp its kernel returned and
  held constant (``index_loss``). Its gradient by the scores is closed,
  ``(softmax_S(I) - p) / rows``, so the rule forms the indexer's three
  gradients in the same pass over the blocks that sums the loss, keeps
  them by name (``RESIDUAL_NAMES``) and its backward only scales them: a
  remat block that keeps those names walks the blocks once a step.

Everything here is XLA but the loss's target: where the caller's kernels run
(``kernel_tile``) a block's ``p`` before its normalisation is one Pallas
call, ``ops/pallas/index_target.py``, whose [heads, rows, keys] logits stay
in VMEM; elsewhere the same lines in XLA (``block_target``), which writes
them to HBM once a span's keys are many. Scopes are
the caller's (``models/mixers.py``: ``index_scores``, ``index_select``,
``index_loss``); ``choose`` opens the first two itself, round the parts of a
block's body.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.util import plans

F32 = jnp.float32

#: rows of scores a block holds: [b, J, rows, s] float32 is 268 MB at J 16,
#: s 16,384
BLOCK_ROWS = 256
#: bits of the threshold a pass over a block settles
_BITS = 2
#: a sequence's rows are walked in this many spans, each against the keys
#: up to its own last row and no further: the blocks of a span are one loop
#: of one static shape, and the keys no row of it can see are not multiplied
#: (eight spans multiply 9/16 of the square)
_SPANS = 8
#: what ``choose`` calls its mask and ``index_loss`` its three gradients
#: (``jax.ad_checkpoint.checkpoint_name``): a ``jax.checkpoint`` whose policy
#: keeps these names runs neither again in its backward
RESIDUAL_NAMES = ("sparse_choice", "index_grad_q", "index_grad_k",
                  "index_grad_w")
#: what a step counts of its choices, int32 each (``choose``): the (query,
#: key) pairs the causal mask leaves, those chosen of them, and the rows
#: where ties at the threshold kept more than ``topk``
COUNTERS = ("index_pairs_live", "index_pairs_chosen", "index_rows_over_k")


def plan(seq: int, heads: int, head_dim: int, topk: int, batch: int = 1,
         target_tile: Optional[int] = None) -> Dict[str, Any]:
    """What ``choose`` and ``index_loss`` do at one shape, noted as
    ``sparse_plan``; pure. ``target_tile``: the keys a grid step of the
    target's kernel (``kernel_tile``), None for XLA's form."""
    rows = _block_rows(seq)
    return {"seq": seq, "index_heads": heads, "index_head_dim": head_dim,
            "topk": topk, "block_rows": rows, "blocks": seq // rows,
            "spans": len(_spans(seq)),
            "threshold_passes": 32 // _BITS,
            "counts_a_pass": 2 ** _BITS - 1,
            "block_score_bytes": 4 * batch * heads * rows * seq,
            "choice_bytes": batch * seq * seq,
            "pairs_live": seq * (seq + 1) // 2,
            "pairs_chosen": chosen_pairs(seq, topk),
            **target_plan(target_tile)}


def target_plan(tile: Optional[int]) -> Dict[str, Any]:
    """``plan``'s two keys that ``index_loss`` decides, and notes."""
    return {"target_impl": "pallas" if tile else "xla", "target_tile": tile}


def kernel_tile(impl: str, seq: int, heads: int, kv_heads: int, d: int,
                itemsize: int) -> Optional[int]:
    """The keys a grid step of ``ops/pallas/index_target.py``'s call where
    it builds the loss's target, None where XLA's lines do: ``impl``
    ``"flash"`` (the caller's ``attn_impl``), one chip
    (``context.single_chip``), ``d`` whole lanes, the block's rows whole
    int8 tiles and every span's keys whole tiles (a span sees a whole
    number of spans' keys, so the tile that divides one divides all)."""
    from ray_tpu.ops.pallas import index_target
    from ray_tpu.parallel.context import single_chip

    if impl != "flash" or not single_chip():
        return None
    return index_target.tile_keys(_block_rows(seq), _spans(seq)[0][1], heads,
                                  kv_heads, d, itemsize)


def chosen_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs of one row of the batch that a choice of ``topk``
    keys a query keeps, ties aside: ``min(t + 1, topk)`` for the query at
    ``t``."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def _block_rows(seq: int) -> int:
    return BLOCK_ROWS if seq % BLOCK_ROWS == 0 else seq


def _spans(seq: int):
    """(first row, rows) of each span: ``_SPANS`` equal ones of whole
    blocks, or the sequence as one."""
    rows = _block_rows(seq)
    if seq % (_SPANS * rows):
        return [(0, seq)]
    return [(i * seq // _SPANS, seq // _SPANS) for i in range(_SPANS)]


def block_scores(q_blk: jax.Array, k_idx: jax.Array, w_blk: jax.Array
                 ) -> jax.Array:
    """``I`` for a block of rows: ``q_blk`` [b, r, J, e], ``k_idx``
    [b, s, e], ``w_blk`` [b, r, J] float32 -> [b, r, s] float32."""
    dots = jnp.einsum("brje,bue->bjru", q_blk, k_idx,
                      preferred_element_type=F32)
    # (+ 0.0: a row of negative weights on idle heads sums to -0.0, which
    # the threshold's integers would order below +0.0)
    return jnp.einsum("bjru,brj->bru", jax.nn.relu(dots), w_blk) + 0.0


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> int32 in the same order (an involution on the bits)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))


_INT_MIN = -2 ** 31


def threshold(scores: jax.Array, seen: jax.Array, topk: int) -> jax.Array:
    """The ``topk``-th largest of each row's ``seen`` entries, exactly:
    ``scores`` [..., s] float32, ``seen`` [..., s] bool -> [...] float32,
    ``-inf`` for a row that sees fewer than ``topk``."""
    keys = jnp.where(seen, _ordered(scores), _INT_MIN)
    levels = 2 ** _BITS - 1

    def settle(i, found):
        # ``found``: the threshold's settled top bits as an unsigned number
        # (uint32); a candidate's order among int32 keys is its bits with
        # the top one turned
        shift = (32 - _BITS * (i + 1)).astype(jnp.uint32)
        reached = jnp.zeros(found.shape, jnp.uint32)
        for level in range(1, levels + 1):
            cand = found | (jnp.uint32(level) << shift)
            at = jax.lax.bitcast_convert_type(
                cand ^ jnp.uint32(0x80000000), jnp.int32)
            n = jnp.sum(keys >= at[..., None], axis=-1, dtype=jnp.int32)
            reached = reached + (n >= topk).astype(jnp.uint32)
        return found | (reached << shift)

    found = jax.lax.fori_loop(0, 32 // _BITS, settle,
                              jnp.zeros(scores.shape[:-1], jnp.uint32))
    at = jax.lax.bitcast_convert_type(found ^ jnp.uint32(0x80000000),
                                      jnp.int32)
    tau = jax.lax.bitcast_convert_type(
        at ^ ((at >> 31) & jnp.int32(0x7FFFFFFF)), F32)
    return jnp.where(found == 0, -jnp.inf, tau)


def _blocks(x: jax.Array, rows: int) -> jax.Array:
    """[b, s, ...] -> [s / rows, b, rows, ...]."""
    b, s = x.shape[:2]
    return jnp.moveaxis(x.reshape(b, s // rows, rows, *x.shape[2:]), 1, 0)


def choose(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array, topk: int
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(the choice [b, s, s] int8, ``tau`` [b, s] float32, ``COUNTERS`` as
    an int32 [3]) of ``q_idx`` [b, s, J, e], ``k_idx`` [b, s, e], ``w``
    [b, s, J].
    No gradient passes: a choice is held fixed."""
    b, s, heads, e = q_idx.shape
    rows = _block_rows(s)
    plans.note("sparse", plan(s, heads, e, topk, b))
    q_idx, k_idx, w = map(jax.lax.stop_gradient, (q_idx, k_idx, w))

    def span(start, n):
        keys = k_idx[:, :start + n]
        key_at = jnp.arange(start + n)

        def block(args):
            q_blk, w_blk, first = args
            with jax.named_scope("index_scores"):
                scores = block_scores(q_blk, keys, w_blk.astype(F32))
            with jax.named_scope("index_select"):
                seen = key_at[None, :] <= (first + jnp.arange(rows))[:, None]
                tau = threshold(scores, seen[None], topk)
                chosen = seen[None] & (scores >= tau[..., None])
                kept = chosen.sum(-1, dtype=jnp.int32)
                return chosen.astype(jnp.int8), tau, jnp.stack(
                    [seen.sum(dtype=jnp.int32) * b, kept.sum(),
                     (kept > topk).sum(dtype=jnp.int32)])

        at = slice(start, start + n)
        chosen, tau, counts = jax.lax.map(
            block, (_blocks(q_idx[:, at], rows), _blocks(w[:, at], rows),
                    jnp.arange(start, start + n, rows)))
        with jax.named_scope("index_select"):
            chosen = jnp.moveaxis(chosen, 0, 1).reshape(b, n, start + n)
            return (jnp.pad(chosen, ((0, 0), (0, 0), (0, s - start - n))),
                    jnp.moveaxis(tau, 0, 1).reshape(b, n), counts.sum(0))

    chosen, tau, counts = zip(*(span(*sp) for sp in _spans(s)))
    with jax.named_scope("index_select"):
        chosen, tau = jnp.concatenate(chosen, 1), jnp.concatenate(tau, 1)
        counted = sum(counts)
    return checkpoint_name(chosen, RESIDUAL_NAMES[0]), tau, counted


def dense_attention(q, k, v, select, scale: float):
    """The main attention under a choice without a kernel (``attn_impl``
    "xla"; the tests' size): q [b, s, h, d], k, v [b, s, hkv, d], ``select``
    [b, s, s] -> (o [b, s, h, d], lse [b, h, s] float32)."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    qg = q.reshape(b, s, k.shape[2], group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg * jnp.asarray(scale, q.dtype),
                        k, preferred_element_type=F32)
    seen = (select != 0) & (jnp.arange(s)[:, None] >= jnp.arange(s)[None, :])
    logits = jnp.where(seen[:, None, None], logits, NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)
    weights = jnp.exp(logits - lse[..., None]).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)
    return out.reshape(b, s, h, d), jax.lax.stop_gradient(
        lse.reshape(b, h, s))


def _loss_and_grads(q_idx, k_idx, w, q, k, lse, select, scale, impl):
    """(``L_I``, its gradients by ``q_idx``, ``k_idx``, ``w``), one pass
    over the blocks of rows."""
    b, s, h, d = q.shape
    rows = _block_rows(s)
    tile = kernel_tile(impl, s, h, k.shape[2], d, q.dtype.itemsize)
    plans.note("sparse", target_plan(tile))
    k_heads = jnp.moveaxis(k, 2, 1)                         # [b, hkv, s, d]
    w = w.astype(F32)
    total, grad_k = jnp.zeros((), F32), jnp.zeros(k_idx.shape, F32)
    grad_q, grad_w = [], []

    def whole(x):  # [blocks, b, rows, ...] -> [b, blocks x rows, ...]
        return jnp.moveaxis(x, 0, 1).reshape(b, -1, *x.shape[3:])

    for start, n in _spans(s):
        end, at = start + n, slice(start, start + n)
        (total, grad_k), (d_q, d_w) = jax.lax.scan(
            functools.partial(_loss_block, k_idx[:, :end], k_heads[:, :, :end],
                              scale, b * s, tile),
            (total, grad_k),
            (jnp.arange(start, end, rows),
             _blocks(q_idx[:, at], rows), _blocks(w[:, at], rows),
             _blocks(q[:, at], rows),
             jnp.moveaxis(lse[:, :, at].reshape(b, h, n // rows, rows), 2, 0),
             _blocks(select[:, at, :end], rows)))
        grad_q.append(whole(d_q))
        grad_w.append(whole(d_w))
    return total / (b * s), (jnp.concatenate(grad_q, 1),
                             grad_k.astype(k_idx.dtype),
                             jnp.concatenate(grad_w, 1))


def block_target(q_m, k_heads, lse_m, sel, scale):
    """The target before its normalisation, XLA's form: ``q_m`` [b, rows,
    h, d], ``k_heads`` [b, hkv, u, d], ``lse_m`` [b, h, rows], ``sel``
    [b, rows, u] bool -> [b, rows, u] float32, the heads' weights under the
    choice, summed. What ``index_target.index_target`` computes tile by
    tile, and what it is tested against."""
    b, rows, h, d = q_m.shape
    hkv = k_heads.shape[1]
    qg = q_m.reshape(b, rows, hkv, h // hkv, d)
    logits = jnp.einsum("brhgd,bhud->bhgru", qg, k_heads,
                        preferred_element_type=F32) * scale
    lse_g = lse_m.reshape(b, hkv, h // hkv, rows)
    return jnp.where(sel[:, None, None],
                     jnp.exp(logits - lse_g[..., None]), 0.0).sum((1, 2))


def _loss_block(k_idx, k_heads, scale, n_rows, tile, carry, args):
    """One block of rows of ``_loss_and_grads`` against the keys its span
    sees, ``k_idx`` [b, u, e] and ``k_heads`` [b, hkv, u, d]: the carry is
    (the rows' summed KL so far, ``k_idx``'s gradient [b, s, e] float32).
    ``tile``: the target's kernel runs, that many keys a grid step."""
    total, grad_k = carry
    first, q_i, w_i, q_m, lse_m, chosen = args
    sel = chosen != 0
    scores, back = jax.vjp(block_scores, q_i, k_idx, w_i)
    # the target: the heads' weights under the choice, summed
    if tile:
        from ray_tpu.ops.pallas.index_target import index_target

        p = index_target(q_m, k_heads, lse_m, chosen, first, scale=scale,
                         tile=tile)
    else:
        p = block_target(q_m, k_heads, lse_m, sel, scale)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    # the indexer's distribution over the same keys
    masked = jnp.where(sel, scores, -jnp.inf)
    log_i = masked - jax.nn.logsumexp(masked, axis=-1, keepdims=True)
    kl = jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                               - jnp.where(sel, log_i, 0.0)), 0.0)
    d_scores = jnp.where(sel, jnp.exp(log_i) - p, 0.0) / n_rows
    d_q, d_k, d_w = back(d_scores)
    grad_k = grad_k.at[:, :k_idx.shape[1]].add(d_k.astype(F32))
    return (total + kl.sum(), grad_k), (d_q, d_w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def index_loss(q_idx, k_idx, w, q, k, lse, select, scale, impl="xla"):
    """``L_I`` (module docstring): ``q_idx`` [b, s, J, e], ``k_idx``
    [b, s, e], ``w`` [b, s, J] float32 the indexer's; ``q`` [b, s, h, d],
    ``k`` [b, s, hkv, d] the main attention's as its kernel took them,
    ``lse`` [b, h, s] what it returned, ``select`` [b, s, s] the choice,
    ``scale`` its softmax's, ``impl`` the caller's ``attn_impl``: under
    ``"flash"`` the target is the kernel's where it runs (``kernel_tile``).
    Gradients reach the first three alone."""
    return _loss_and_grads(q_idx, k_idx, w, q, k, lse, select, scale, impl)[0]


def _index_loss_fwd(q_idx, k_idx, w, q, k, lse, select, scale, impl):
    loss, grads = _loss_and_grads(q_idx, k_idx, w, q, k, lse, select, scale,
                                  impl)
    return loss, tuple(map(checkpoint_name, grads, RESIDUAL_NAMES[1:]))


def _index_loss_bwd(scale, impl, grads, ct):
    g_q, g_k, g_w = grads
    return ((ct * g_q).astype(g_q.dtype), (ct * g_k).astype(g_k.dtype),
            ct * g_w, None, None, None, None)


index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)
