"""Three mixers of the patterned walk (``models/moe.py``) beside llama's
attention half, each a pre-norm branch that its caller joins to the residual
stream (``llama.join``), trained and not served: a delta-rule linear
attention whose state decays a channel (``"kda"``, Kimi Delta Attention),
latent attention in its uncompressed form (``"mla"``), unrotated or with a
rotary part, and grouped-query attention over the positions a learned
indexer picks (``"sparse"``, DeepSeek Sparse Attention as Keye-VL-2.0 sizes
it).

A ``kda`` layer, ``h`` the normed input, H heads of width ``dk = dv``:

- ``q, k, v = silu(conv4(h @ wq)), silu(conv4(h @ wk)), silu(conv4(h @ wv))``,
  each its own depthwise causal convolution (no bias); a head's ``q`` and
  ``k`` L2-normalised over their width, ``q`` times ``dk ** -0.5``;
- log-decay a channel ``g = -exp(A_log)[head] * softplus((h @ f_down) @
  f_up + dt_bias)`` and step ``beta = sigmoid(h @ wb)``, float32;
- the recurrence (``ops/kda.py``, chunked; everything of a chunk that does
  not read the state the Pallas kernel pair of ``ops/pallas/kda_insides.py``
  where the plan's ``impl`` says so);
- ``o = rms(o, o_norm) * sigmoid((h @ g_down) @ g_up + g_bias)`` over each
  head's ``dv``, then ``wo``.

Which form the elementwise chains round the recurrence take is the plan's
``mix`` (``ops/kda.plan``, noted beside ``impl``). ``"pallas"``: the four
chains that follow a product (q's, k's and v's convolution, SiLU and norm,
the decay, the output's norm and gate) are the kernels of
``ops/pallas/kda_mix.py``, each one pass over its stream forward and one
backward, a tile float32 from its load to its store and rounded once: where
``attn_impl`` is not ``"xla"``, the head is whole lanes of 128, the taps are
at most ``kda.MAX_CONV_TAPS`` and no mesh of several chips is ambient (the
compiler does not partition a Mosaic call). ``"xla"``, everywhere else:
``ops/ssm.causal_conv``, ``jax.nn.silu`` and ``ops/norms.rmsnorm`` as
written below, which round to the compute dtype at every step and sum a
norm in float32; it is the kernels' oracle in the tests and the form GSPMD
partitions.

An ``mla`` layer, H heads: ``q = h @ wq`` [H, nope + rope], or through a
low rank where the config has ``q_lora_rank``: ``q = rms(h @ wq_a, q_norm)
@ wq_b``; ``[c, k_pe] = h @ wkv_a`` [rank], [rope]; ``c = rms(c, kv_norm)``;
``[k_nope, v] = c @ wkv_b`` [H, nope + dv]; a head's key is ``[k_nope ;
k_pe]``, ``k_pe`` one for all heads. Without ``mla_rope`` nothing is rotated
(the ``rope`` columns are plain columns); with it ``k_pe`` and each head's
last ``rope`` query columns are rotated (``ops/rope.apply_rope``,
interleaved pairs) by tables the walk makes once (``mla_rope_tables``), at
YaRN's blended frequencies where the config has ``mla_yarn``, whose factor
also multiplies the softmax's scale. Causal softmax at ``(nope + rope) **
-0.5`` with values ``dv`` wide (the flash kernels' two widths,
``ops/pallas/flash.py``); ``wo``. Nothing is absorbed and nothing cached:
this is the form a training step runs.

A ``sparse`` layer, H heads over Hkv key/value heads of ``head_dim``, an
indexer of J heads of width e over one key a position: ``q, k, v = h @ wq,
h @ wk, h @ wv``; an RMSNorm over each head's q and k (gains ``q_norm``,
``k_norm`` [head_dim]); q and k rotated by sections (``ops/rope.py``:
frequency pair i takes the position stream of its ``rope_sections``
section); on ``stop_gradient(h)``: ``qI = h @ index_wq`` [J, e], ``kI =
LayerNorm(h @ index_wk)`` [e], both rotated whole by the same streams (the
sections scaled to e / 2 pairs), ``w = h @ index_ww`` [J] float32 times ``J
** -0.5 * e ** -0.5``; the choice of at most ``index_topk`` keys a query,
ties aside, and the indexer's loss (``ops/sparse_index.py``); causal
softmax at ``head_dim ** -0.5`` over the chosen keys alone, the flash
kernels under the choice as a mask (``ops/pallas/flash.py``'s ``select=``)
or, ``attn_impl`` "xla", the dense form; ``wo``. The trunk takes its
gradient through the choice held fixed; the indexer's five leaves take
theirs from its loss alone. The loss rebuilds its target, the attention's
weights summed over the heads, a block of rows at a time from ``q``, ``k``
and the kernel's log-sum-exp: under ``attn_impl`` "flash" on one chip, heads
of whole lanes and whole tiles of rows and keys as one Pallas call a block
(``ops/pallas/index_target.py``: the logits stay in VMEM), else in XLA
(``sparse_index.kernel_tile`` is the rule, ``sparse_plan`` says which); the
indexer's scores, their vjp and the KL are XLA either way.

Scopes are names only. The walk opens ``attn_kda`` / ``attn_mla`` round a
layer's mixer half; inside ``attn_kda`` lie ``kda_conv``, ``kda_gates`` and
``kda_scan`` (the recurrence, forward and backward, and nothing else),
inside ``attn_mla`` ``mla_latent`` (the down- and up-projections and the
latent's norm). ``sparse_half`` opens its own, none inside another:
``attn_sparse`` (the projections, head norms, rotation, the kernels and
``wo``), ``index_scores`` (the indexer's projections and its scores for the
choice), ``index_select`` (the threshold and the mask) and ``index_loss``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import post_norm
from ray_tpu.ops import kda, sparse_index
from ray_tpu.ops.attention import mha
from ray_tpu.ops.norms import layernorm, rmsnorm
from ray_tpu.ops.rope import (apply_rope, apply_rope_by_position, rope_angles,
                              rope_angles_by_sections)
from ray_tpu.ops.ssm import causal_conv

Params = Dict[str, Any]
F32 = jnp.float32

#: added under the square root of the L2 norm of a head's q and k
L2_EPS = 1e-6
#: ``A_log`` is drawn as the log of uniform(A_RANGE) and ``dt_bias`` as the
#: inverse softplus of exp(uniform(log DT_RANGE)): Mamba's initialisation,
#: which spreads the initial decays over (0.85, 0.99999) a token
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, F32) / math.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------- kda

def kda_params(cfg) -> int:
    """One layer's ``kda`` leaves (the norm before the branch left out)."""
    d, h, w, t = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_taps
    return (4 * d * h * w + 3 * t * h * w          # q k v o and their taps
            + 2 * (d * w + w * h * w) + h * w      # the two low-rank pairs, g_bias
            + d * h + h + h * w + w)               # wb, A_log, dt_bias, o_norm


def init_kda(rng: jax.Array, cfg, n: int) -> Params:
    """``n`` layers' ``kda`` leaves, stacked. ``A_log`` and ``dt_bias`` are
    float32 whatever the parameters' dtype: eight bits of mantissa would
    move a decay near 1 by more than it forgets."""
    d, h, w, t = cfg.d_model, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_taps
    ch, dt = h * w, cfg.param_dtype
    ks = jax.random.split(rng, 14)
    step = jnp.exp(jax.random.uniform(
        ks[12], (n, ch), F32, math.log(DT_RANGE[0]), math.log(DT_RANGE[1])))
    return {
        "wq": _normal(ks[0], (n, d, ch), d, dt),
        "wk": _normal(ks[1], (n, d, ch), d, dt),
        "wv": _normal(ks[2], (n, d, ch), d, dt),
        "conv_q": _normal(ks[3], (n, t, ch), t, dt),
        "conv_k": _normal(ks[4], (n, t, ch), t, dt),
        "conv_v": _normal(ks[5], (n, t, ch), t, dt),
        "f_down": _normal(ks[6], (n, d, w), d, dt),
        "f_up": _normal(ks[7], (n, w, ch), w, dt),
        "wb": _normal(ks[8], (n, d, h), d, dt),
        "g_down": _normal(ks[9], (n, d, w), d, dt),
        "g_up": _normal(ks[10], (n, w, ch), w, dt),
        "g_bias": jnp.zeros((n, ch), dt),
        "A_log": jnp.log(jax.random.uniform(ks[11], (n, h), F32, *A_RANGE)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "o_norm": jnp.ones((n, w), dt),
        "wo": _normal(ks[13], (n, ch, d), ch, dt),
    }


def kda_half(cfg, x: jax.Array, layer: Params) -> jax.Array:
    """Pre-norm KDA's branch, [b, s, d] -> [b, s, d]; the caller opens
    ``attn_kda`` round it (module docstring) and joins it to the stream."""
    from ray_tpu.parallel.context import single_chip

    b, s, _ = x.shape
    h, w, cdt = cfg.kda_heads, cfg.kda_head_dim, cfg.compute_dtype
    taps = cfg.kda_conv_taps
    impl = cfg.attn_impl if single_chip() else "xla"
    fused = kda.plan(s, h, w, w, b, impl=impl, conv_taps=taps)["mix"] == "pallas"
    if fused:
        # here and not at the module's top: a process that traces no step
        # with the kernels never loads them
        from ray_tpu.ops.pallas import kda_mix

        # the kernels hand the recurrence its streams heads first, as it
        # works: this view and ``kda_chunked``'s own turn cancel, and none
        # is transposed through HBM
        heads_second = functools.partial(jnp.moveaxis, source=1, destination=2)
    hx = rmsnorm(x, layer["attn_norm"].astype(cdt), cfg.norm_eps)

    with jax.named_scope("kda_conv"):
        if fused:
            def mixed(which, unit, scale=1.0):
                return heads_second(kda_mix.conv_silu_unit(
                    hx @ layer["w" + which].astype(cdt),
                    layer["conv_" + which].astype(cdt), w, unit, scale,
                    L2_EPS))

            q, k, v = mixed("q", True, w ** -0.5), mixed("k", True), \
                mixed("v", False)
        else:
            tail = jnp.zeros((b, taps - 1, h * w), cdt)

            def mixed(which):
                y, _ = causal_conv(hx @ layer["w" + which].astype(cdt), tail,
                                   layer["conv_" + which].astype(cdt), 0.0)
                return jax.nn.silu(y).reshape(b, s, h, w)

            def unit(y):  # a head's L2 norm, summed in float32
                y32 = y.astype(F32)
                return (y32 * jax.lax.rsqrt(
                    jnp.sum(y32 * y32, -1, keepdims=True) + L2_EPS)).astype(cdt)

            q, k, v = unit(mixed("q")) * jnp.asarray(w ** -0.5, cdt), \
                unit(mixed("k")), mixed("v")

    with jax.named_scope("kda_gates"):
        a = (hx @ layer["f_down"].astype(cdt)) @ layer["f_up"].astype(cdt)
        if fused:
            g = heads_second(kda_mix.decay(
                a, layer["dt_bias"].astype(F32),
                jnp.repeat(-jnp.exp(layer["A_log"].astype(F32)), w), w))
        else:
            a = a.astype(F32) + layer["dt_bias"].astype(F32)
            g = -jnp.exp(layer["A_log"].astype(F32))[:, None] \
                * jax.nn.softplus(a).reshape(b, s, h, w)
        beta = jax.nn.sigmoid((hx @ layer["wb"].astype(cdt)).astype(F32))

    with jax.named_scope("kda_scan"):
        o = kda.kda_chunked(q, k, v, g, beta, impl=impl, conv_taps=taps)

    gate = (hx @ layer["g_down"].astype(cdt)) @ layer["g_up"].astype(cdt)
    if fused:
        o = kda_mix.norm_gate(
            jnp.moveaxis(o, 2, 1), layer["o_norm"].astype(cdt), gate,
            layer["g_bias"].astype(cdt), cfg.norm_eps)
    else:
        gate = jax.nn.sigmoid(
            gate + layer["g_bias"].astype(cdt)).reshape(b, s, h, w)
        o = (rmsnorm(o, layer["o_norm"].astype(cdt), cfg.norm_eps)
             * gate).reshape(b, s, h * w)
    return post_norm(cfg, o @ layer["wo"].astype(cdt), layer,
                     "attn_post_norm")


# ---------------------------------------------------------------------- mla

def mla_params(cfg) -> int:
    """One layer's ``mla`` leaves (the norm before the branch left out)."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    rq = cfg.q_lora_rank
    wq = d * rq + rq + rq * h * (nope + rope) if rq else d * h * (nope + rope)
    return (wq + d * (r + rope) + r + r * h * (nope + dv) + h * dv * d)


def init_mla(rng: jax.Array, cfg, n: int) -> Params:
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt, rq = cfg.param_dtype, cfg.q_lora_rank
    ks = jax.random.split(rng, 4)
    if rq:
        wq = {"wq_a": _normal(ks[0], (n, d, rq), d, dt),
              "q_norm": jnp.ones((n, rq), dt),
              "wq_b": _normal(jax.random.fold_in(rng, 4),
                              (n, rq, h * (nope + rope)), rq, dt)}
    else:
        wq = {"wq": _normal(ks[0], (n, d, h * (nope + rope)), d, dt)}
    return {
        **wq,
        "wkv_a": _normal(ks[1], (n, d, r + rope), d, dt),
        "kv_norm": jnp.ones((n, r), dt),
        "wkv_b": _normal(ks[2], (n, r, h * (nope + dv)), r, dt),
        "wo": _normal(ks[3], (n, h * dv, d), h * dv, dt),
    }


def mla_rope_tables(cfg, seq: int):
    """(sin, cos) [seq, rope // 2] of an ``mla`` layer's rotary part in the
    compute dtype, or None where the config rotates nothing there."""
    if not cfg.mla_rope:
        return None
    return rope_angles(seq, cfg.qk_rope_head_dim, cfg.rope_theta,
                       cfg.compute_dtype, yarn=cfg.mla_yarn)


def mla_half(cfg, x: jax.Array, layer: Params, segment_ids,
             tables=None) -> jax.Array:
    """Pre-norm latent attention's branch, [b, s, d] -> [b, s, d];
    ``tables``: ``mla_rope_tables``'s."""
    b, s, _ = x.shape
    h, r, cdt = cfg.n_heads, cfg.kv_lora_rank, cfg.compute_dtype
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hx = rmsnorm(x, layer["attn_norm"].astype(cdt), cfg.norm_eps)

    with jax.named_scope("mla_latent"):
        if cfg.q_lora_rank:
            cq = rmsnorm(hx @ layer["wq_a"].astype(cdt),
                         layer["q_norm"].astype(cdt), cfg.norm_eps)
            q = cq @ layer["wq_b"].astype(cdt)
        else:
            q = hx @ layer["wq"].astype(cdt)
        q = q.reshape(b, s, h, nope + rope)
        down = hx @ layer["wkv_a"].astype(cdt)
        c = rmsnorm(down[..., :r], layer["kv_norm"].astype(cdt), cfg.norm_eps)
        up = (c @ layer["wkv_b"].astype(cdt)).reshape(b, s, h, nope + dv)
        k_nope, k_pe = up[..., :nope], down[:, :, None, r:]
        if tables is not None:
            q = jnp.concatenate(
                [q[..., :nope], apply_rope(q[..., nope:], *tables)], axis=-1)
            k_pe = apply_rope(k_pe, *tables)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_pe, (b, s, h, rope))], axis=-1)
        v = up[..., nope:]

    # None, the kernels' own default, where the config has no YaRN record
    scale = None if cfg.mla_yarn is None else (
        (nope + rope) ** -0.5 * cfg.mla_yarn.softmax_scale())
    if cfg.attn_impl == "flash":
        if segment_ids is not None:
            raise NotImplementedError(
                "segment_ids (packed sequences) require attn_impl='xla'")
        from ray_tpu.parallel.context import flash_attention_on_mesh

        attn = flash_attention_on_mesh(q, k, v, causal=True, scale=scale)
    elif cfg.attn_impl == "xla":
        attn = mha(q, k, v, causal=True, segment_ids=segment_ids, scale=scale)
    else:
        raise NotImplementedError(
            f"latent attention under attn_impl={cfg.attn_impl!r}: a sequence "
            f"split across chips has no ring at two head widths")
    return post_norm(cfg, attn.reshape(b, s, h * dv)
                     @ layer["wo"].astype(cdt), layer, "attn_post_norm")


# ------------------------------------------------------------------- sparse

def sparse_params(cfg) -> int:
    """One layer's ``sparse`` leaves (the norm before the branch left out):
    the four projections and the two head norms, the indexer's three
    matrices and its LayerNorm's gain and bias."""
    d, q = cfg.d_model, cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    j, e = cfg.index_heads, cfg.index_head_dim
    return (2 * d * q + 2 * d * kv + 2 * cfg.head_dim
            + d * (j * e + e + j) + 2 * e)


def init_sparse(rng: jax.Array, cfg, n: int) -> Params:
    d, hd, dt = cfg.d_model, cfg.head_dim, cfg.param_dtype
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    j, e = cfg.index_heads, cfg.index_head_dim
    ks = jax.random.split(rng, 7)
    return {
        "wq": _normal(ks[0], (n, d, q), d, dt),
        "wk": _normal(ks[1], (n, d, kv), d, dt),
        "wv": _normal(ks[2], (n, d, kv), d, dt),
        "wo": _normal(ks[3], (n, q, d), q, dt),
        "q_norm": jnp.ones((n, hd), dt),
        "k_norm": jnp.ones((n, hd), dt),
        "index_wq": _normal(ks[4], (n, d, j * e), d, dt),
        "index_wk": _normal(ks[5], (n, d, e), d, dt),
        "index_ww": _normal(ks[6], (n, d, j), d, dt),
        "index_k_norm": jnp.ones((n, e), dt),
        "index_k_norm_b": jnp.zeros((n, e), dt),
    }


def sparse_rope_tables(cfg, batch: int, seq: int, positions=None):
    """((sin, cos) [b, s, head_dim / 2] for the main attention's heads,
    the same for the indexer's), in the compute dtype, from ``positions``
    [sections, b, s]: one position stream a ``rope_sections`` entry, every
    stream ``arange(seq)`` where a batch carries none (text-only rows)."""
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(seq), (len(cfg.rope_sections), batch, seq))
    return tuple(rope_angles_by_sections(
        positions, width, cfg.rope_theta, cfg.rope_sections, cfg.compute_dtype)
        for width in (cfg.head_dim, cfg.index_head_dim))


def _indexer_inputs(cfg, hx: jax.Array, layer: Params, tables):
    """The indexer's (queries [b, s, J, e], keys [b, s, e], weights
    [b, s, J] float32) of a layer's normed input ``hx``, which is held
    constant; under ``index_scores``. ``tables``: the indexer's rotary ones."""
    b, s, _ = hx.shape
    j, e, cdt = cfg.index_heads, cfg.index_head_dim, cfg.compute_dtype
    with jax.named_scope("index_scores"):
        hs = jax.lax.stop_gradient(hx)
        q_idx = apply_rope_by_position(
            (hs @ layer["index_wq"].astype(cdt)).reshape(b, s, j, e), *tables)
        k_idx = apply_rope_by_position(layernorm(
            hs @ layer["index_wk"].astype(cdt),
            layer["index_k_norm"].astype(cdt),
            layer["index_k_norm_b"].astype(cdt),
            cfg.norm_eps)[:, :, None, :], *tables)[:, :, 0, :]
        w = (hs @ layer["index_ww"].astype(cdt)).astype(F32) \
            * (j ** -0.5 * e ** -0.5)
    return q_idx, k_idx, w


def sparse_choice(cfg, x: jax.Array, layer: Params, tables):
    """What a ``sparse`` layer's indexer picks for the stream ``x`` [b, s,
    d]: (the choice [b, s, s] int8, the thresholds [b, s] float32), by the
    functions ``sparse_half`` runs, for a reader that wants a layer's choice
    beside its output (the tests, the chip check)."""
    hx = rmsnorm(x, layer["attn_norm"].astype(cfg.compute_dtype), cfg.norm_eps)
    return sparse_index.choose(*_indexer_inputs(cfg, hx, layer, tables[1]),
                               cfg.index_topk)[:2]


def sparse_half(cfg, x: jax.Array, layer: Params, segment_ids, tables):
    """Pre-norm attention over the indexer's choice, [b, s, d] -> (the
    branch [b, s, d], the indexer's loss, ``sparse_index.COUNTERS`` as an
    int32 [3]); ``tables``: ``sparse_rope_tables``'s. Opens its own scopes
    (module docstring)."""
    from ray_tpu.parallel.context import single_chip

    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids (packed sequences) through a sparse layer: the "
            "indexer would have to choose inside a document")
    b, s, _ = x.shape
    h, hkv, hd, cdt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.compute_dtype
    topk = cfg.index_topk
    main, index = tables

    with jax.named_scope("attn_sparse"):
        hx = rmsnorm(x, layer["attn_norm"].astype(cdt), cfg.norm_eps)
        q = rmsnorm((hx @ layer["wq"].astype(cdt)).reshape(b, s, h, hd),
                    layer["q_norm"].astype(cdt), cfg.norm_eps)
        k = rmsnorm((hx @ layer["wk"].astype(cdt)).reshape(b, s, hkv, hd),
                    layer["k_norm"].astype(cdt), cfg.norm_eps)
        v = (hx @ layer["wv"].astype(cdt)).reshape(b, s, hkv, hd)
        q, k = apply_rope_by_position(q, *main), apply_rope_by_position(k, *main)

    q_idx, k_idx, w = _indexer_inputs(cfg, hx, layer, index)
    chosen, _, counted = sparse_index.choose(q_idx, k_idx, w, topk)

    with jax.named_scope("attn_sparse"):
        if cfg.attn_impl == "flash":
            if not single_chip():
                raise NotImplementedError(
                    "a sparse layer's kernels under a mesh of several chips: "
                    "the choice [b, s, s] would have to follow q's shards")
            from ray_tpu.ops.pallas.flash import flash_attention_chosen

            attn, lse = flash_attention_chosen(q, k, v, chosen, topk=topk)
        elif cfg.attn_impl == "xla":
            attn, lse = sparse_index.dense_attention(q, k, v, chosen,
                                                     hd ** -0.5)
        else:
            raise NotImplementedError(
                f"a sparse layer under attn_impl={cfg.attn_impl!r}: a "
                f"sequence split across chips has no ring under a choice")

    with jax.named_scope("index_loss"):
        loss = sparse_index.index_loss(
            q_idx, k_idx, w, jax.lax.stop_gradient(q),
            jax.lax.stop_gradient(k), lse, chosen, hd ** -0.5,
            cfg.attn_impl)

    with jax.named_scope("attn_sparse"):
        branch = post_norm(cfg, attn.reshape(b, s, h * hd)
                           @ layer["wo"].astype(cdt), layer, "attn_post_norm")
    return branch, loss, counted
