"""Autoregressive generation with a static KV cache — the inference path.

TPU-first decode (no reference counterpart — Ray ships no model code; this
is the standard JAX recipe): the cache is a STATIC [L, B, max_len, kv_heads,
head_dim] buffer, prefill runs the whole prompt as one batched forward
(MXU-friendly), and the decode loop is a single ``lax.scan`` over steps —
one compiled program regardless of how many tokens are generated.
Causality over the not-yet-written cache tail falls out of
``mha(q_offset=pos)``'s mask. GQA works unchanged (the cache holds kv
heads).

Two forwards share one block arithmetic (``_qkv``, ``_after_attention``):

- ``_forward_with_cache``: [B, S] tokens at ONE position for all rows,
  the cache as the layer scan's ``xs``/``ys``, each layer writing its S
  new positions with ``dynamic_update_slice``. Prefill and ``generate``.
- ``decode_step_in_place``: one token per row at PER-ROW positions, the
  whole cache in the layer scan's carry, written only at the new
  positions (one indexed update a layer) and read where it lies, and of
  each row only the positions below the step's ``kv_read_bound``: the
  furthest row's position rounded up to a ``KV_CHUNK``, reckoned from
  ``pos`` once a step, so a launch whose rows live at 700 of 2048
  allocated positions reads 768 of each. The bound picks one of
  ``max_len / KV_CHUNK`` branches inside the one program (no program per
  length); a position left unread is one the causal mask zeroes. The
  serving engine's decode step, whose cache is donated: nothing the size
  of the cache is copied.

Works for both model families: llama densely, MoE through
``moe.served_ffn_half`` (drop-free routing whose work follows the routed
tokens), whose per-layer routing counters the ``_stats`` forms return.

A model with recurrent layers (``models/hybrid.py``, ``models/sambay.py``)
keeps, beside the keys and values of its attention layers, a state and a
convolution tail for each recurrent layer, and one with window layers a ring
of the last ``sliding_window`` keys and values for each of those: the cache
is a tree of buffers by layer kind (``init_cache``), and
``_forward_with_cache_stats`` and ``decode_step_on_slots`` hand such a model
to its module (``hybrid.model_of``), which builds its attention layers from
the pieces here.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ray_tpu.models import llama
from ray_tpu.ops.attention import mha
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_angles

Params = Dict[str, Any]


def init_cache(cfg: llama.LlamaConfig, batch: int, max_len: int) -> Dict:
    """The zeroed cache of ``batch`` rows, a tree of buffers by layer kind:
    ``k`` and ``v`` [L_attention, B, max_len, kv_heads, head_dim] (compute
    dtype; a row's three sizes are the config's ``kv_shape(max_len)``) for
    the layers that keep every position, ``wk`` and ``wv`` [L_window, B,
    ``kv_shape(sliding_window)``] for window layers' rings, and for a model with
    recurrent layers their ``ssm`` state and ``conv`` tail as well (its
    module's ``init_state``): ``cache_names``'s buffers."""
    llama.refuse_trained_only(cfg)
    shape = (cfg.n_attention_layers, batch, *cfg.kv_shape(max_len))
    cache = {"k": jnp.zeros(shape, cfg.compute_dtype),
             "v": jnp.zeros(shape, cfg.compute_dtype)}
    if cfg.n_window_layers:
        if max_len < cfg.sliding_window:
            raise ValueError(
                f"max_len {max_len} under the sliding window "
                f"{cfg.sliding_window}: a window layer's ring is the window "
                f"long whatever max_len is, and a cache shorter than it is "
                f"no deployment of this model")
        ring = (cfg.n_window_layers, batch,
                *cfg.kv_shape(cfg.sliding_window))
        cache.update(wk=jnp.zeros(ring, cfg.compute_dtype),
                     wv=jnp.zeros(ring, cfg.compute_dtype))
    if cfg.n_recurrent_layers:
        from ray_tpu.models import hybrid

        cache.update(hybrid.model_of(cfg).init_state(cfg, batch))
    return cache


def cache_names(cfg) -> Tuple[str, ...]:
    """``init_cache``'s buffers in the order every engine program takes and
    returns them (a dict that went through ``jax.tree`` comes back sorted:
    nothing may go by a dict's own order)."""
    return (("k", "v") + (("wk", "wv") if cfg.n_window_layers else ())
            + (("ssm", "conv") if cfg.n_recurrent_layers else ()))


embed = llama.embed


def _qkv(cfg, x, layer, sin, cos, positions):
    """The attention half up to the cache: pre-norm, the three projections
    and rope at ``positions`` [B, S] (none where the config rotates
    nothing: ``sin`` and ``cos`` are then not looked at). Returns q
    [B, S, hq, hd] and k, v [B, S, hkv, hd]."""
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype
    if cfg.attn_impl in ("ring", "ulysses"):
        raise NotImplementedError(
            f"decode with attn_impl={cfg.attn_impl!r} (sequence-parallel "
            f"attention) is not supported — single-token decode has no "
            f"sequence to shard. 'flash' and 'xla' configs both decode via "
            f"the einsum path (same math; the pallas kernel is a "
            f"long-sequence training implementation).")
    h = rmsnorm(x, layer["attn_norm"].astype(cdt), cfg.norm_eps)
    def rotated(y):
        return apply_rope(y, sin, cos, positions) if cfg.use_rope else y

    q = rotated(llama.project_qk(cfg, h, layer, "q").reshape(b, s, hq, hd))
    k = rotated(llama.project_qk(cfg, h, layer, "k").reshape(b, s, hkv, hd))
    v = (h @ layer["wv"].astype(cdt)).reshape(b, s, hkv, hd)
    return q, k, v


def _rope_table(cfg, max_len: int):
    """(sin, cos) up to ``max_len``, or (None, None) without rotation."""
    if not cfg.use_rope:
        return None, None
    return rope_angles(max_len, cfg.head_dim, cfg.rope_theta,
                       cfg.compute_dtype)


def _split_experts(layers: Params) -> Tuple[Params, Optional[Params]]:
    """(what a layer scan slices a layer at a time, what it must leave
    whole): a sparse model's stacked expert matrices are read where they
    lie (``moe.served_ffn_half``); a dense model has none."""
    if "router" not in layers:
        return layers, None
    from ray_tpu.models import moe

    return ({k: v for k, v in layers.items() if k not in moe.EXPERT_WEIGHTS},
            {k: layers[k] for k in moe.EXPERT_WEIGHTS})


def _after_attention(cfg, x, attn, layer, experts=None, index=None):
    """The rest of the block: output projection and residual (still the
    ``attn`` scope's), then the feed-forward half. Returns (hidden,
    stats): a sparse layer's routing counters (``moe.SERVED_STATS``), a
    dense layer's None. ``experts`` and ``index``: ``_split_experts``'s
    second part and which layer this is."""
    b, s, _ = x.shape
    with jax.named_scope("attn"):
        x = x + llama.on_residual(
            cfg, attn.reshape(b, s, -1) @ layer["wo"].astype(cfg.compute_dtype))
    if "w_gate" in layer:  # dense llama FFN (shared ffn_half)
        with jax.named_scope("mlp"):
            return llama.join(x, llama.ffn_half(cfg, x, layer)), None
    # MoE FFN: drop-free inference routing under its own four scopes
    from ray_tpu.models import moe

    return moe.served_ffn_half(cfg, x, layer, experts, index)


def _fold_stats(stats):
    """The layers' stacked routing counters as one launch-sized vector."""
    if stats is None:
        return None
    from ray_tpu.models import moe

    return moe.fold_served_stats(stats)


def _block_with_cache(cfg, x, layer, cache_k, cache_v, sin, cos, pos,
                      experts=None, index=None):
    """One decoder block over [B, S, d] at absolute position ``pos``,
    reading/writing the layer's [B, max_len, hkv, hd] cache slices.
    Returns (hidden, new_cache_k, new_cache_v, stats)."""
    b, s, _ = x.shape
    # scopes are names only: they group the block's operations in a
    # device trace (``attn``, ``mlp``) and change nothing that is computed
    with jax.named_scope("attn"):
        positions = jnp.broadcast_to(pos + jnp.arange(s)[None, :], (b, s))
        q, k, v = _qkv(cfg, x, layer, sin, cos, positions)
        cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, pos, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, pos, 0, 0))
        attn = mha(q, cache_k, cache_v, causal=True, q_offset=pos,
                   scale=cfg.attn_scale)
    x, stats = _after_attention(cfg, x, attn, layer, experts, index)
    return x, cache_k, cache_v, stats


def _head(params: Params, cfg, x):
    """Final norm and the vocabulary projection, float32 logits."""
    cdt = cfg.compute_dtype
    wide = x.dtype != cdt  # a residual stream kept in float32 (sambay.py)
    x = llama.pre_norm(cfg, x, params, "final_norm")
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cdt)
    if wide:  # the logits as they were summed, not rounded on the way
        logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)
    else:
        logits = (x @ head).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def _forward_with_cache(params: Params, tokens: jax.Array,
                        cfg, cache: Dict, pos,
                        last_only: bool = True) -> Tuple[jax.Array, Dict]:
    """``_forward_with_cache_stats`` without the routing counters."""
    return _forward_with_cache_stats(params, tokens, cfg, cache, pos,
                                     last_only)[:2]


def _forward_with_cache_stats(params: Params, tokens: jax.Array,
                              cfg, cache: Dict, pos,
                              last_only: bool = True
                              ) -> Tuple[jax.Array, Dict, Optional[jax.Array]]:
    """tokens [B, S] at absolute position ``pos`` -> (logits, updated
    cache, the sparse layers' routing counters or None). ``last_only``
    projects ONLY the final position to the vocab — generation never needs
    the full [B, S, V] prefill logits, which at 32k vocab would dominate
    HBM (the same blowup llama's loss_chunk avoids)."""
    if cfg.n_recurrent_layers:
        from ray_tpu.models import hybrid

        return (*hybrid.model_of(cfg).forward_with_cache(
            params, tokens, cfg, cache, pos, last_only), None)
    x = embed(params, cfg, tokens)
    max_len = cache["k"].shape[2]
    sin, cos = _rope_table(cfg, max_len)

    layers, experts = _split_experts(params["layers"])

    def body(carry, sl):
        x = carry
        layer, ck, cv, *index = sl
        x, ck, cv, stats = _block_with_cache(cfg, x, layer, ck, cv, sin, cos,
                                             pos, experts, *index)
        return x, (ck, cv, stats)

    xs = (layers, cache["k"], cache["v"])
    if experts is not None:  # a sparse layer has to know which it is
        xs += (jnp.arange(cfg.n_layers),)
    x, (new_k, new_v, stats) = jax.lax.scan(body, x, xs)
    with jax.named_scope("head_sample"):
        logits = _head(params, cfg, x[:, -1:, :] if last_only else x)
    return logits, {"k": new_k, "v": new_v}, _fold_stats(stats)


#: positions a bounded read advances by (``kv_read_bound``): a multiple of
#: the chip's 128 lanes, since positions are the lane dimension wherever the
#: chip keeps K and V position-minor (head 64)
KV_CHUNK = 256


def kv_read_bound(pos, max_len: int, xp=jnp):
    """How many positions of each row a decode step's attention reads: the
    furthest row's ``pos + 1``, rounded up to whole ``KV_CHUNK``s, at most
    ``max_len`` (a row past its end holds nothing up: it writes nothing and
    nobody reads its token). ``pos`` [B] are the step's rows' positions, a
    free slot's among them (the batcher keeps those at 0). ``xp`` is
    ``jnp`` in the program and ``numpy`` in the recorder, which counts what
    the program read from the positions it staged: one rule for both."""
    furthest = xp.minimum(xp.max(pos), max_len - 1) + 1
    return xp.minimum(-(-furthest // KV_CHUNK) * KV_CHUNK, max_len)


def kv_read_bounds(max_len: int) -> Tuple[int, ...]:
    """Every value ``kv_read_bound`` takes at ``max_len``, ascending."""
    return tuple(min(n, max_len)
                 for n in range(KV_CHUNK, max_len + KV_CHUNK, KV_CHUNK))


def decode_step_in_place(params: Params, tok: jax.Array, cfg,
                         ck: jax.Array, cv: jax.Array, slot0,
                         pos: jax.Array) -> Tuple[jax.Array, jax.Array,
                                                  jax.Array]:
    """``decode_step_in_place_stats`` without the routing counters."""
    return decode_step_in_place_stats(params, tok, cfg, ck, cv, slot0,
                                      pos)[:3]


def decode_step_in_place_stats(params: Params, tok: jax.Array, cfg,
                               ck: jax.Array, cv: jax.Array, slot0,
                               pos: jax.Array
                               ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                          Optional[jax.Array]]:
    """One decode step for the ``B`` cache rows ``slot0 .. slot0 + B`` of
    a slot cache ``ck``/``cv`` [L, slots, max_len, hkv, hd]: ``tok`` [B]
    is each row's token AT its own position ``pos`` [B]. Returns (logits
    [B, V] float32, ck, cv, the sparse layers' routing counters or None).

    The block arithmetic is ``_block_with_cache``'s; what differs is the
    cache's way through the step. It rides the layer scan's carry, each
    layer writes its rows' new K and V ([B, hkv, hd]) at ``(layer, row,
    pos)`` with one indexed update and attention reads the layer's rows
    out of the same buffer, below the step's ``kv_read_bound`` only — with
    the cache donated by the caller the update is in place and nothing
    cache-sized is stacked, transposed or gathered. A position past
    ``max_len`` (a row that finished earlier in a fused launch, whose
    tokens nobody reads) writes nothing."""
    max_len = ck.shape[2]
    x = embed(params, cfg, tok)[:, None, :]
    sin, cos = _rope_table(cfg, max_len)
    rows = slot0 + jnp.arange(tok.shape[0])
    bound = kv_read_bound(pos, max_len)  # once a step, for every layer

    def body(carry, sl):
        x, ck, cv = carry
        layer, l = sl
        x, ck, cv, stats = attend_in_place(cfg, x, layer, ck, cv, l, slot0,
                                           rows, pos, sin, cos, bound,
                                           experts)
        return (x, ck, cv), stats

    layers, experts = _split_experts(params["layers"])
    (x, ck, cv), stats = jax.lax.scan(
        body, (x, ck, cv), (layers, jnp.arange(cfg.n_layers)))
    with jax.named_scope("head_sample"):
        logits = _head(params, cfg, x)[:, 0, :]
    return logits, ck, cv, _fold_stats(stats)


def attend_in_place(cfg, x, layer, ck, cv, l, slot0, rows, pos, sin, cos,
                    bound, experts=None):
    """One attention block of a decode step, in place on the slot cache:
    ``x`` [B, 1, d] are the cache's rows ``rows`` = ``slot0 .. slot0 + B``,
    each at its own position ``pos`` [B]; layer ``l`` of ``ck``/``cv``
    takes their new K and V with one indexed update and is read where it
    lies, below ``bound`` (the step's ``kv_read_bound``: every position a
    row attends to lies below it): one branch for each value the bound
    takes, each the slice-and-``mha`` of the whole row cut at its own static
    length. A position a branch leaves unread is one the causal mask zeroes:
    its score adds exactly 0 to the float32 softmax. Returns (hidden, ck,
    cv, stats)."""
    # with branches to choose between, what they read and give is held to
    # the layout it arrives in: left free, the chip's compiler re-lays the
    # whole cache round the conditional (a cache-sized copy a branch, and
    # for a lone row one a layer at 16 x its size: PERF.md, PR 32)
    held = held_as_it_lies(ck.shape[2])

    with jax.named_scope("attn"):
        q, k, v = _qkv(cfg, x, layer, sin, cos, pos[:, None])
        ck = held(ck.at[l, rows, pos].set(k[:, 0], mode="drop"))
        cv = held(cv.at[l, rows, pos].set(v[:, 0], mode="drop"))
        attn = read_below(q, ck, cv, l, slot0, pos, bound,
                          scale=cfg.attn_scale)
    # (the output projection too: after a conditional the lone row's
    # program transposed the whole ``wo`` stack, every launch)
    layer = {**layer, "wo": held(layer["wo"])}
    x, stats = _after_attention(cfg, x, attn, layer, experts, l)
    return x, ck, cv, stats


def read_below(q, ck, cv, l, slot0, pos, bound, axis: int = 2, **how):
    """``mha`` of ``q`` [B, 1, hq, hd], the rows ``slot0 .. slot0 + B`` at
    their positions ``pos`` [B], over layer ``l`` of ``ck``/``cv`` read
    where it lies, below ``bound`` (``attend_in_place`` says how). Writes
    nothing: a layer that attends to another's keys and values
    (``models/sambay.py``) is this alone. ``axis``: where positions lie in
    ``ck`` (the config's ``kv_length_axis``); ``how``: ``mha``'s other
    arguments."""
    b = q.shape[0]
    max_len = ck.shape[axis]
    lengths = kv_read_bounds(max_len)
    held = held_as_it_lies(max_len)

    def reading(n):
        size = (1, b) + ck.shape[2:axis] + (n,) + ck.shape[axis + 1:]

        def layer_rows(cache):  # [B, n, hkv, hd], where they lie
            return held(jax.lax.dynamic_slice(
                cache, (l, slot0, 0, 0, 0), size)[0])

        return lambda: mha(q, layer_rows(ck), layer_rows(cv), causal=True,
                           q_offset=pos, **how)

    return jax.lax.switch((bound - 1) // KV_CHUNK,
                          [reading(n) for n in lengths])


def held_as_it_lies(max_len: int):
    """``_as_it_lies`` where a read of ``max_len`` positions has branches
    to choose between, the identity where it has one."""
    return _as_it_lies if len(kv_read_bounds(max_len)) > 1 else lambda x: x


def _as_it_lies(x: jax.Array) -> jax.Array:
    """``x`` held to the row-major layout a program's arguments have."""
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def decode_step_on_slots(params: Params, tok: jax.Array, cfg, cache: Dict,
                         slot0, pos: jax.Array
                         ) -> Tuple[jax.Array, Dict, Optional[jax.Array]]:
    """``decode_step_in_place_stats`` on the slot cache as the tree of
    buffers ``init_cache`` makes: (logits, the tree, routing counters or
    None). A model with recurrent layers steps their state in place as
    well (``hybrid.decode_step_in_place``)."""
    if cfg.n_recurrent_layers:
        from ray_tpu.models import hybrid

        return (*hybrid.model_of(cfg).decode_step_in_place(
            params, tok, cfg, cache, slot0, pos), None)
    logits, ck, cv, stats = decode_step_in_place_stats(
        params, tok, cfg, cache["k"], cache["v"], slot0, pos)
    return logits, {"k": ck, "v": cv}, stats


def generate(params: Params, prompt: jax.Array, cfg,
             *, max_new_tokens: int, temperature: float = 0.0,
             top_k: Optional[int] = None,
             key: Optional[jax.Array] = None,
             max_len: Optional[int] = None) -> jax.Array:
    """prompt [B, S] -> generated tokens [B, max_new_tokens].

    ``temperature == 0``: greedy. Otherwise softmax sampling (optionally
    top-k truncated) with ``key``. The whole loop is one jit: prefill +
    ``lax.scan`` over decode steps.
    """
    b, s = prompt.shape
    total = max_len or (s + max_new_tokens)
    if total < s + max_new_tokens:
        raise ValueError(f"max_len {total} < prompt {s} + new {max_new_tokens}")
    if temperature > 0 and key is None:
        key = jax.random.key(0)
    run = _compiled_generate(cfg, b, s, total, max_new_tokens,
                             float(temperature), top_k)
    return run(params, prompt, key)


def _sample_token(last_logits, temperature: float, top_k: Optional[int],
                  key):
    """Greedy (temperature<=0) or temperature/top-k categorical sampling —
    the ONE sampling rule, which the engine's per-row form follows."""
    if temperature <= 0:
        return jnp.argmax(last_logits, axis=-1)
    scaled = last_logits / temperature
    if top_k is not None:
        kth = jnp.sort(scaled, axis=-1)[:, -top_k][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled)


@functools.lru_cache(maxsize=64)
def _compiled_generate(cfg, b: int, s: int, total: int, max_new_tokens: int,
                       temperature: float, top_k: Optional[int]):
    """One compiled program per (config, shapes, sampling) — repeat calls
    (the serve per-request path) hit jit's cache instead of re-tracing.
    Configs are frozen dataclasses, hence hashable cache keys."""

    @jax.jit
    def run(params, prompt, key):
        cache = init_cache(cfg, b, total)
        logits, cache = _forward_with_cache(params, prompt, cfg, cache, 0)
        last = logits[:, -1, :]

        def step(carry, i):
            cache, last_logits, key = carry
            if key is not None:
                key, sub = jax.random.split(key)
            else:
                sub = None
            tok = _sample_token(last_logits, temperature, top_k, sub)
            logits, cache = _forward_with_cache(
                params, tok[:, None], cfg, cache, s + i)
            return (cache, logits[:, -1, :], key), tok

        (_, _, _), toks = jax.lax.scan(
            step, (cache, last, key), jnp.arange(max_new_tokens))
        return toks.swapaxes(0, 1)  # [B, T]

    return run
