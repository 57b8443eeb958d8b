"""EVA attention ("Efficient Attention via Control Variates",
arXiv:2302.04542) in the deterministic form the EvaByte release trains: exact
attention inside a window, one learned summary a chunk of every earlier
window, one softmax over both.

With ``c`` the chunk and ``w`` the window (``w`` a whole number of chunks), a
head ``a`` and a chunk ``j`` (positions ``c j .. c j + c - 1``):

    alpha_m = softmax_m(phi_a . k_m)            over the chunk's c positions
    k~_j    = mu_a + sum_m alpha_m k_m
    v~_j    = sum_m alpha_m v_m

(``summaries``; ``phi_a``, ``mu_a`` [head_dim] are learned; ``k`` is the
rotated key). A query at position t, ``W(t) = t // w``, sees the keys
``{k_m : W(m) = W(t), m <= t}`` and the summaries ``{k~_j : (c j) // w <
W(t)}``: the chunks of its own window are NOT summarised for it. One softmax
over both sets at ``1/sqrt(head_dim)``; the output is ``sum p_m v_m + sum
p_j v~_j`` (``visible`` is that rule as a mask, ``eva_attention`` the whole,
from the projections' results to the mixer's output).

Two paths that share no logic, chosen by ``impl``:

``"pallas"``  (one chip, ``attn_impl="flash"``) two kernel families.
    ``ops/pallas/eva_mix.py``: RoPE on q and k, the turn heads first (padded
    to whole windows) and the chunk summaries as one call forward and one
    backward, each stream once through HBM, float32 inside. Then
    ``ops/pallas/eva_attn.py``'s four attention kernels.
``"xla"``     (the CPU tests' yardstick, and what a mesh of several chips
    runs, because a Mosaic call is not partitioned) ``ops/rope.apply_rope``,
    a transpose a stream, ``summaries`` under plain autodiff (float32
    inside, rounded to the inputs' dtype) and a dense masked softmax over
    ``[s, s + s / c]`` scores.

Scopes: ``eva_mix`` (the first family) or ``eva_summaries`` (XLA's), and
``eva_attend``, which ``models/llama.eva_half`` puts inside ``attn_eva``.

What a remat block can keep of this (``RESIDUAL_NAMES``): q, k and v as the
attention reads them (rotated, heads first) and the summaries: the five
results of ``eva_mix``'s forward call, which names them itself and whose
backward reads of them the rotated k and v alone (the rotation's pull-back
needs the tables, the pooling's the rotated k, v and ``phi``), so that a
block that saves by these names runs no projection a second time. The
attention kernels' ``o`` and ``lse`` carry ``flash.RESIDUAL_NAMES``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import NEG_INF
from ray_tpu.ops.rope import apply_rope
from ray_tpu.util import plans

RESIDUAL_NAMES = ("eva_q", "eva_k", "eva_v", "eva_ks", "eva_vs")
#: the release's ``init_std``: ``phi`` and ``mu`` start as a normal of this
#: deviation cut off at one deviation (``models/llama.init_params``)
INIT_STD = 0.01275

F32 = jnp.float32


# ---------------------------------------------------------------- the plan

def plan(seq: int, heads: int, head_dim: int, window: int, chunk: int,
         batch: int = 1, impl: str = "pallas") -> Dict[str, Any]:
    """What ``eva_attention`` does at one shape; pure. ``windows`` of
    ``window`` positions (the last may be part full: the sequence is padded
    to whole windows), ``chunks`` summaries a row of which a query sees at
    most ``summaries_seen``; ``tiles_needed``: (q block, key block) tiles of
    one head, at the kernels' block sizes, that hold a visible pair (the
    summaries' part and the windows' causal halves); ``tiles_visited``: those
    the implementation computes, the same for the kernels, every tile of the
    ``[s, s + s / chunk]`` rectangle for ``impl="xla"``; ``mix``: what makes
    the attention's operands of the projections' results, ``"pallas"``
    (``ops/pallas/eva_mix.py``'s call pair) with the kernels, else
    ``"xla"``."""
    from ray_tpu.ops.pallas import eva_attn

    if seq % chunk:
        raise ValueError(
            f"eva_attention: a sequence of {seq} positions is not a whole "
            f"number of chunks of {chunk} (eva_chunk)")
    if window % chunk:
        raise ValueError(f"eva_attention: window {window} is not a whole "
                         f"number of chunks of {chunk}")
    windows, bpw, block = eva_attn.tiling(seq, window)
    per_window = window // chunk
    need = eva_attn.tiles_needed(windows, bpw)
    needed = need["summary"] + need["local"]
    visited = needed if impl == "pallas" else (windows * bpw) * (
        windows * bpw + windows)
    return {"impl": impl, "mix": impl, "batch": batch, "heads": heads,
            "head_dim": head_dim,
            "seq": seq, "window": window, "chunk": chunk, "windows": windows,
            "chunks": seq // chunk, "summaries_seen": (windows - 1) * per_window,
            "block": block, "summary_block": per_window,
            "tiles_needed": needed, "tiles_visited": visited}


def visible_pairs(seq: int, window: int, chunk: int):
    """(query, key) and (query, summary) pairs of one head that ``visible``
    leaves alive in a ``seq``-position row: each window's causal half, and
    window i (from 0) against the ``i * window / chunk`` summaries before."""
    whole, rest = divmod(seq, window)
    local = (whole * window * (window + 1) + rest * (rest + 1)) // 2
    pooled = (window // chunk) * (window * whole * (whole - 1) // 2
                                  + rest * whole)
    return local, pooled


# ---------------------------------------------------------------- the parts

def summaries(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
              chunk: int, made=None):
    """k, v [n, s, d] a head a row of n (``phi``, ``mu`` [n, d], the head's
    own) -> (k~, v~) [n, s // chunk, d] in the inputs' dtype. ``made``: the
    pair where a kernel has formed it from the same operands already
    (``eva_mix``'s forward call); it is handed on as it is, so that both
    paths' summaries enter the attention here and nowhere else (what cuts
    their cotangent here cuts it in both:
    ``tests/benchmark/evabyte_chip_check.py``'s planted fault)."""
    if made is not None:
        return made
    n, s, d = k.shape
    kc = k.reshape(n, s // chunk, chunk, d).astype(F32)
    vc = v.reshape(n, s // chunk, chunk, d).astype(F32)
    logits = jnp.sum(kc * phi.astype(F32)[:, None, None, :], axis=-1)
    alpha = jax.nn.softmax(logits, axis=-1)[..., None]     # [n, chunks, c, 1]
    ks = mu.astype(F32)[:, None, :] + jnp.sum(alpha * kc, axis=2)
    return ks.astype(k.dtype), jnp.sum(alpha * vc, axis=2).astype(v.dtype)


def visible(seq: int, window: int, chunk: int) -> jax.Array:
    """bool [seq, seq + seq // chunk]: which keys (the first ``seq`` columns)
    and which summaries (the rest) the query of each row sees."""
    t = jnp.arange(seq)[:, None]
    m = jnp.arange(seq)[None, :]
    j = jnp.arange(seq // chunk)[None, :]
    keys = (m // window == t // window) & (m <= t)
    return jnp.concatenate([keys, (j * chunk) // window < t // window], axis=1)


def _attend_dense(q, k, v, ks, vs, *, window, chunk, scale):
    """The dense form on [n, s, d]: one masked softmax over keys and
    summaries."""
    s = q.shape[1]
    keys = jnp.concatenate([k, ks], axis=1)
    scores = jnp.einsum("nqd,nkd->nqk", q, keys,
                        preferred_element_type=F32) * scale
    scores = jnp.where(visible(s, window, chunk)[None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("nqk,nkd->nqd", p, jnp.concatenate([v, vs], axis=1),
                      preferred_element_type=F32).astype(q.dtype)


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, sin: jax.Array,
                  cos: jax.Array, phi: jax.Array, mu: jax.Array, *,
                  window: int, chunk: int, impl: str = "pallas") -> jax.Array:
    """q, k, v [b, s, h, d], the projections' results (not rotated);
    ``sin``, ``cos`` ``ops/rope.rope_angles``' tables; ``phi``, ``mu`` [h,
    d] -> [b, s, h, d]. ``s`` must be a whole number of chunks; a last
    window that is part full is padded with keys no real query sees and
    rows that are cut off."""
    b, s, h, d = q.shape
    p = plan(s, h, d, window, chunk, batch=b, impl=impl)
    plans.note("eva", p)
    if impl == "pallas":
        from ray_tpu.ops.pallas import eva_attn, eva_mix

        with jax.named_scope("eva_mix"):
            q, k, v, ks, vs = eva_mix.mix(
                *(a.reshape(b, s, h * d) for a in (q, k, v)), sin, cos, phi,
                mu, window, chunk)
        ks, vs = summaries(k, v, phi, mu, chunk, (ks, vs))
        with jax.named_scope("eva_attend"):
            o = eva_attn.eva_attend(q, k, v, ks, vs, window=window,
                                    chunk=chunk, scale=d ** -0.5)
        return o[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)
    pad = p["windows"] * window - s

    def heads_first(a):  # [b, s, h, d] -> [b * h, padded s, d]
        a = a.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        return jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a

    q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)
    q, k, v = (checkpoint_name(heads_first(a), name)
               for a, name in zip((q, k, v), RESIDUAL_NAMES))
    with jax.named_scope("eva_summaries"):
        ks, vs = summaries(k, v, jnp.tile(phi, (b, 1)), jnp.tile(mu, (b, 1)),
                           chunk)
        ks, vs = map(checkpoint_name, (ks, vs), RESIDUAL_NAMES[3:])
    with jax.named_scope("eva_attend"):
        o = _attend_dense(q, k, v, ks, vs, window=window, chunk=chunk,
                          scale=d ** -0.5)
    return o[:, :s].reshape(b, h, s, d).transpose(0, 2, 1, 3)
