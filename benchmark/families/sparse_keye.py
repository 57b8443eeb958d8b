"""The KeyeVL2 family's language model: Keye-VL-2.0-30B-A3B's block as
Kwai-Keye publish it (``config.json``, ``model_type`` ``KeyeVL2``: a
Qwen3-MoE-shaped block, which the keys spell out one for one, under the
DeepSeek-Sparse-Attention indexer that ``sa_config`` sizes) and
``ray_tpu/models/moe.py`` trains it in its patterned form, kind ``sparse``.
(Named to sort after ``moe.py``: ``tests/benchmark/test_benchmark_spec.py``
holds the sorted directory to begin ``dense.py``, ``moe.py``.)

A layer, all alike, ``x`` [s, d] the residual stream, float32 here, every
product at ``highest``:

- ``h = rms(x, attn_norm)``; ``q = h wq`` [32, 128], ``k = h wk``,
  ``v = h wv`` [4, 128]; ``rms`` over each head's 128 of q and of k (gains
  ``q_norm``, ``k_norm``); q and k rotated by sections (``_rotate``:
  frequency pair i of 64, ``theta ** (-2 i / 128)``, takes the position
  stream of its ``mrope_section`` 16 | 24 | 24; interleaved pairs);
- the indexer, on ``stop_gradient(h)``: ``qI = h index_wq`` [16, 64],
  ``kI = LayerNorm(h index_wk)`` [64], ``w = h index_ww`` [16] times
  ``16 ** -0.5 * 64 ** -0.5``; qI and kI rotated whole by the same streams,
  32 pairs, the sections scaled to 8 | 12 | 12; ``I[t, u] = sum_j w[t, j]
  relu(qI[t, j] . kI[u])`` for ``u <= t``;
- ``tau[t]`` = the ``topk``-th largest of ``I[t, 0..t]`` by a sort, ``-inf``
  while the row has fewer; ``S_t = {u <= t : I[t, u] >= tau[t]}``;
- each of the 32 heads, over its group's keys: ``o[t] = sum_{u in S_t}
  softmax_{u in S_t}(q[t] . k[u] / sqrt(128)) v[u]``; ``x + o wo``;
- ``L_I = mean_t KL(p[t] || softmax_{u in S_t} I[t, u])``, ``p`` the heads'
  weights summed, L1-normalised over ``S_t``, held constant;
- ``h2 = rms(x, mlp_norm)``; ``scores = softmax(h2 router)`` over all 128
  published experts; the top 8, gates over their sum; ``x + sum_{e held
  here} gate_e SwiGLU_e(h2)``, capacity in queue order as the program's
  buffers drop (every token's first choice, then every second, ...);
- ``loss = ce + balance_coefficient * aux + index_loss_coef * mean over the
  layers of L_I``.

Departures from the published model, each set out in the configuration
file's ``assumed``: ties at ``tau`` are all kept (measure zero at float32);
the head norms, the indexer's ``relu``, LayerNorm and scale, the rotation's
pairing and the scaled sections are the sibling releases' (Qwen3-MoE,
DeepSeek-V3.2-Exp), from memory; no FP8 and no Hadamard turn; the balancing
term's form and both coefficients; the vision tower is left out and
text-only rows feed three equal position streams.

The chip's share is as the afmoe family's: ``config`` holds the keys as run
(``num_experts`` held here, ``vocab_size`` the slice) with
``num_experts_published`` beside them. Importing this file imports neither
JAX nor the program; its functions do, and the reference imports nothing of
``ray_tpu``.
"""

import functools
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import spec

# ---- the program's config and weights ----------------------------------------

# what ``ray_tpu/models/moe.py``'s config class must have for this family
NEEDS = ("index_heads", "index_topk", "rope_sections")
# the leaves of a layer's mixer, under ``sparse`` in a segment's tree
INDEX_LEAVES = ("index_wq", "index_wk", "index_ww", "index_k_norm",
                "index_k_norm_b")


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program cannot build
    this family's config (``ray_tpu/models/moe.py`` before PR 63 has none
    of the fields, and no layer kind ``sparse``), as the afmoe family's
    does: called by the cell's new readers as the parent process loads
    them, so that a checkout that cannot train the cell fails in seconds,
    before it starts a trainer. Reads the source and imports nothing of
    JAX."""
    import os
    import re

    import ray_tpu

    path = os.path.join(os.path.dirname(ray_tpu.__file__), "models", "moe.py")
    with open(path) as f:
        source = f.read()
    for field in NEEDS:
        if not re.search(rf"^\s+{field}\s*:", source, re.M):
            raise spec.SpecError(
                f"family sparse_keye needs the config field {field!r} and "
                f"the layer kind 'sparse', which {path} does not have: this "
                f"checkout's program cannot run it")


def _published_experts(hf: Dict[str, Any]) -> int:
    return hf.get("num_experts_published", hf["num_experts"])


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import moe

    require_program()
    hf, assumed = cfg_file["config"], cfg_file["assumed"]
    sa = hf["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise spec.SpecError("an indexer of more than one key a position")
    return moe.MoEConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], attn_head_dim=hf["head_dim"],
        d_ff=hf["moe_intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(hf["rope_theta"]), norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk,
        qk_norm_head=True, layer_kinds=("sparse",) * n_layers,
        n_experts=_published_experts(hf), n_experts_held=hf["num_experts"],
        top_k=hf["num_experts_per_tok"], router_score="softmax",
        norm_topk_prob=bool(hf["norm_topk_prob"]), balance="sequence",
        router_aux_coef=float(assumed["balance_coefficient"]),
        capacity_factor=float(assumed["capacity_factor"]),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        index_loss_coef=float(assumed["index_loss_coef"]),
        rope_sections=tuple(hf["rope_scaling"]["mrope_section"]))


def init_params(rng, cfg):
    from ray_tpu.models import moe

    return moe.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

QUERY_BLOCK = 256  # rows at once: 32 heads x 256 x 16384 float32 is 537 MB


def _static(cfg_file: Dict[str, Any], capacity_factor: Optional[float]) -> Tuple:
    """What a compiled layer reads of the configuration, hashable."""
    hf = cfg_file["config"]
    sa = hf["sa_config"]
    return (
        ("rms_norm_eps", hf["rms_norm_eps"]),
        ("heads", hf["num_attention_heads"]),
        ("kv_heads", hf["num_key_value_heads"]),
        ("head_dim", hf["head_dim"]),
        ("rope_theta", hf["rope_theta"]),
        ("sections", tuple(hf["rope_scaling"]["mrope_section"])),
        ("index_heads", sa["indexer_num_heads"]),
        ("index_head_dim", sa["indexer_head_dim"]),
        ("topk", sa["topk"]),
        ("num_experts_published", _published_experts(hf)),
        ("num_experts", hf["num_experts"]),
        ("num_experts_per_tok", hf["num_experts_per_tok"]),
        ("norm_topk_prob", hf["norm_topk_prob"]),
        ("capacity_factor", capacity_factor))


def section_of_pair(sections, half: int):
    """The position stream of each of ``half`` frequency pairs: the first
    ``sections[0]`` pairs the first stream's and so on, the sections scaled
    where the head has fewer pairs than they add up to."""
    total = sum(sections)
    out = []
    for stream, n in enumerate(sections):
        out += [stream] * (n * half // total)
    if len(out) != half:
        raise spec.SpecError(f"sections {sections} do not scale to {half}")
    return out


def _rotate(x, positions, theta: float, sections):
    """x [b, s, h, d]: the pairs (x[2i], x[2i+1]) turned by ``theta ** (-2 i
    / d)`` times the position of pair i's stream; ``positions``
    [streams, b, s] float32."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    stream = jnp.asarray(section_of_pair(sections, d // 2))
    ang = jnp.moveaxis(positions, 0, -1)[..., stream] * inv      # [b, s, d/2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _layernorm(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def indexer_inputs(h, layer, hf: Dict[str, Any], positions):
    """(``qI`` [b, s, J, e], ``kI`` [b, s, e], ``w`` [b, s, J]) of the
    normed input ``h`` [b, s, d]."""
    b, s, _ = h.shape
    j, e = hf["index_heads"], hf["index_head_dim"]
    q = _rotate((h @ layer["index_wq"]).reshape(b, s, j, e), positions,
                hf["rope_theta"], hf["sections"])
    k = _rotate(_layernorm(h @ layer["index_wk"], layer["index_k_norm"],
                           layer["index_k_norm_b"],
                           hf["rms_norm_eps"])[:, :, None, :], positions,
                hf["rope_theta"], hf["sections"])[:, :, 0, :]
    return q, k, (h @ layer["index_ww"]) * (j ** -0.5 * e ** -0.5)


def index_scores(q, k, w):
    """``I`` [b, r, s] float32 of a block of rows' ``q`` [b, r, J, e] and
    ``w`` [b, r, J] against every key ``k`` [b, s, e] (no mask applied)."""
    import jax.numpy as jnp

    return jnp.einsum("btj,bjtu->btu", w, jnp.maximum(
        jnp.einsum("btje,bue->bjtu", q, k), 0.0))


def choice_of(scores, first: int, topk: int):
    """(``S`` [b, r, s] bool, ``tau`` [b, r]) of ``scores`` [b, r, s], the
    rows ``first .. first + r``: by a sort of each row's causal past; ties
    at ``tau`` all kept."""
    import jax.numpy as jnp

    r, s = scores.shape[-2:]
    causal = jnp.arange(s)[None, :] <= (first + jnp.arange(r))[:, None]
    seen = jnp.where(causal, scores, -jnp.inf)
    if s >= topk:
        tau = jnp.sort(seen, axis=-1)[..., s - topk]
    else:
        tau = jnp.full(scores.shape[:-1], -jnp.inf, scores.dtype)
    return causal & (seen >= tau[..., None]), tau


def _attention(x, layer, hf: Dict[str, Any], positions, chosen=None):
    """The attention half, residual included, and the indexer's loss, a
    block of queries at a time (a block's scores are rebuilt in a backward,
    not kept): (x', L_I, the choice [b, s, s] bool, tau [b, s]).
    ``chosen``: a choice to attend under in place of the indexer's own (the
    program's, in the tests)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = x.shape
    hq, hkv, hd = hf["heads"], hf["kv_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]
    h = ref.rms(x, layer["attn_norm"], eps)
    q = ref.rms((h @ layer["wq"]).reshape(b, s, hq, hd), layer["q_norm"], eps)
    k = ref.rms((h @ layer["wk"]).reshape(b, s, hkv, hd), layer["k_norm"], eps)
    v = (h @ layer["wv"]).reshape(b, s, hkv, hd)
    q = _rotate(q, positions, hf["rope_theta"], hf["sections"])
    k = _rotate(k, positions, hf["rope_theta"], hf["sections"])
    qi, ki, wi = indexer_inputs(jax.lax.stop_gradient(h), layer, hf, positions)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    own = chosen is None
    if own:
        chosen = jnp.zeros((b, s, 0), bool)

    @jax.checkpoint
    def rows(args):  # a block of queries from row ``first``
        qb, qib, wib, sel, first = args
        scores = index_scores(qib, ki, wib)                       # [b, r, s]
        mine, tau = choice_of(jax.lax.stop_gradient(scores), first,
                              hf["topk"])
        sel = mine if own else sel
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / jnp.sqrt(ref.F32(hd))
        p = jax.nn.softmax(jnp.where(sel[:, None, None], logits, -jnp.inf), -1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
        target = jax.lax.stop_gradient(p.sum((1, 2)))
        target = target / target.sum(-1, keepdims=True)
        log_i = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), -1)
        kl = jnp.where(sel & (target > 0), target * (
            jnp.log(jnp.where(target > 0, target, 1.0))
            - jnp.where(sel, log_i, 0.0)), 0.0)
        return out, kl.sum(), sel, tau

    def cut(a):  # [b, s, ...] -> [s / block, b, block, ...]
        return a.reshape(b, s // block, block, *a.shape[2:]).swapaxes(0, 1)

    out, kl, chosen, tau = jax.lax.map(rows, (
        cut(q.reshape(b, s, hkv, hq // hkv, hd)), cut(qi), cut(wi),
        cut(chosen), jnp.arange(0, s, block)))
    out = out.swapaxes(0, 1).reshape(b, s, hq * hd)
    return (x + out @ layer["wo"], kl.sum() / (b * s),
            chosen.swapaxes(0, 1).reshape(b, s, s),
            tau.swapaxes(0, 1).reshape(b, s))


def _experts(h, layer, hf: Dict[str, Any], b: int,
             held: Optional[Tuple[int, int]] = None):
    """h [G, d], the normed input -> (the routed experts' sum [G, d], the
    balancing term). ``held``: (first, count) of the experts whose part is
    summed, the first ``num_experts`` by default; ``layer``'s expert leaves
    hold exactly those."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    g = h.shape[0]
    e_all, top_k = hf["num_experts_published"], hf["num_experts_per_tok"]
    first, count = held or (0, hf["num_experts"])
    scores = jax.nn.softmax(h @ layer["router"], axis=-1)
    top_s, top_i = jax.lax.top_k(scores, top_k)
    if hf["norm_topk_prob"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    picked = jax.nn.one_hot(top_i, e_all, dtype=jnp.int32)  # [G, K, E]
    if hf["capacity_factor"] is not None:
        # a token's place in its expert's queue: all first choices in token
        # order, then all second choices; places beyond the capacity drop
        cap = max(1, int(hf["capacity_factor"] * g * top_k / e_all))
        order = picked.transpose(1, 0, 2).reshape(top_k * g, e_all)
        place = (jnp.cumsum(order, axis=0) - order).reshape(top_k, g, e_all)
        place = (place.transpose(1, 0, 2) * picked).sum(-1)  # [G, K]
        top_s = top_s * (place < cap)
    weight = jnp.einsum("gk,gke->ge", top_s, picked.astype(ref.F32))
    y = jnp.zeros_like(h)
    for e in range(count):  # the experts that live elsewhere add nothing here
        one = functools.partial(ref.swiglu, gate=layer["e_gate"][e],
                                up=layer["e_up"][e], down=layer["e_down"][e])
        y = y + weight[:, first + e, None] * ref.in_chunks(one, h)
    # a sequence at a time, over all K choices and all published experts
    share = picked.sum(1).astype(ref.F32).reshape(b, -1, e_all).mean(1)
    mass = (scores / scores.sum(-1, keepdims=True)).reshape(b, -1, e_all).mean(1)
    return y, (e_all / top_k) * jnp.mean(jnp.sum(share * mass, axis=-1))


def _block(x, layer, hf: Dict[str, Any], positions, chosen=None):
    """One layer -> (x', the balancing term, L_I, the choice, tau)."""
    from benchmark.lib import reference as ref

    b, s, d = x.shape
    x, index_loss, chosen, tau = _attention(x, layer, hf, positions, chosen)
    h = ref.rms(x, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
    f, aux = _experts(h, layer, hf, b)
    return x + f.reshape(b, s, d), aux, index_loss, chosen, tau


def _layer_fn():
    """The compiled layer, built on first use (importing this file imports
    no JAX). ``layers`` is the segment's tree as the program keeps it: a
    layer's own leaves and its mixer's under ``sparse``, at ``index``."""
    import jax

    @functools.partial(jax.jit, static_argnames=("static", "keep"))
    def layer_fn(x, layers, index, positions, chosen, *, static, keep):
        def at(a):
            return jax.lax.dynamic_index_in_dim(
                a, index, 0, False).astype(jax.numpy.float32)

        with jax.default_matmul_precision("highest"):
            layer = {name: at(a) for name, a in layers.items()
                     if not isinstance(a, dict)}
            layer.update(jax.tree.map(at, layers["sparse"]))
            out = _block(x, layer, dict(static), positions, chosen)
            return out if keep else out[:3]

    return layer_fn


_layer = None


def _positions(tokens, positions):
    import jax.numpy as jnp

    if positions is None:  # text-only rows: three equal streams
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                     (3, *tokens.shape))
    return positions.astype(jnp.float32)


def hidden(params, tokens, cfg_file: Dict[str, Any],
           capacity_factor: Optional[float] = None, round_to=None,
           positions=None, choices=None, keep: bool = False):
    """tokens [b, s] -> (final-norm hidden [b, s, d] float32, mean of the
    layers' balancing terms, mean of their ``L_I``, and with ``keep`` the
    layers' choices [L, b, s, s] bool and thresholds [L, b, s], else None
    twice) over as many layers as ``params`` holds.
    ``round_to`` a dtype: every weight and the residual stream after every
    layer pass through it, which is this reference computed in that
    precision (the loss limit's control). ``positions`` [3, b, s]: the three
    position streams. ``choices`` [L, b, s, s]: a choice a layer to attend
    under in place of the indexer's own."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    global _layer
    if _layer is None:
        _layer = _layer_fn()
    static = _static(cfg_file, capacity_factor)
    if round_to is not None:
        params = jax.tree.map(lambda a: a.astype(round_to), params)
    positions = _positions(tokens, positions)
    x = params["embed"][tokens].astype(ref.F32)
    n = params["layers"]["attn_norm"].shape[0]
    aux = index = ref.F32(0)
    kept = []
    for i in range(n):
        x, a, l, *own = _layer(x, params["layers"], jnp.int32(i), positions,
                               None if choices is None else choices[i] != 0,
                               static=static, keep=keep)
        if round_to is not None:
            x = x.astype(round_to).astype(ref.F32)
        aux, index = aux + a, index + l
        kept.append(own)
    x = ref.rms(x, params["final_norm"].astype(ref.F32),
                cfg_file["config"]["rms_norm_eps"])
    chose, tau = map(jnp.stack, zip(*kept)) if keep else (None, None)
    return x, aux / n, index / n, chose, tau


def logits(params, tokens, cfg_file: Dict[str, Any], choices=None):
    """Float32 logits [b, s, V] over the slice, routing without drops."""
    from benchmark.lib import reference as ref

    x = hidden(params, tokens, cfg_file, choices=choices)[0]
    return ref._project(x, params["lm_head"])


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's, routing without drops."""
    from benchmark.lib import reference as ref

    x = hidden(params, tokens, cfg_file)[0]
    return ref._margins(x[0], params["lm_head"], following)


def loss(params, tokens, cfg_file: Dict[str, Any], round_to=None,
         positions=None, choices=None):
    """Of tokens [b, s+1] over the slice, under the capacity that
    ``assumed`` sets: ``ce``, the balancing term ``aux`` and the indexers'
    ``index_loss``, each meaned over the layers; ``loss = ce +
    balance_coefficient aux + index_loss_coef index_loss``. ``round_to``,
    ``positions``, ``choices``: as ``hidden``'s."""
    from benchmark.lib import reference as ref

    assumed = cfg_file["assumed"]
    x, aux, index, _, _ = hidden(params, tokens[:, :-1], cfg_file,
                                 assumed.get("capacity_factor"),
                                 round_to=round_to, positions=positions,
                                 choices=choices)
    head = params["lm_head"]
    if round_to is not None:
        head = head.astype(round_to)
    ce = ref._sequence_nll(x, tokens[:, 1:], head)
    return {"loss": (ce + assumed["balance_coefficient"] * aux
                     + assumed["index_loss_coef"] * index),
            "ce": ce, "aux": aux, "index_loss": index}


def loss_and_grads(params, tokens, cfg_file: Dict[str, Any], positions=None,
                   choices=None, term: str = "loss"):
    """(``term`` of ``loss``'s dict, its gradient by ``jax.grad`` through
    the reference, float32 leaf for leaf as ``params``'s tree)."""
    import jax
    import jax.numpy as jnp

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return jax.value_and_grad(lambda p: loss(
        p, tokens, cfg_file, positions=positions, choices=choices)[term])(params)


# ---- the arithmetic ------------------------------------------------------------

def attention_matmul_params(hf: Dict[str, Any]) -> int:
    """One layer's four attention matrices: q, k, v and o."""
    d = hf["hidden_size"]
    q = hf["num_attention_heads"] * hf["head_dim"]
    return 2 * d * q + 2 * d * hf["num_key_value_heads"] * hf["head_dim"]


def indexer_matmul_params(hf: Dict[str, Any]) -> int:
    """One layer's indexer: its queries', its key's and its weights'."""
    sa = hf["sa_config"]
    return hf["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms left out): a
    layer's attention, its indexer, the router at its published width and
    the routed experts held here, or (``active_only``) the visits a token
    pays them on average: ``num_experts_per_tok * held / published``
    experts' worth."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    published = _published_experts(hf)
    routed = (hf["num_experts_per_tok"] * hf["num_experts"] / published
              if active_only else hf["num_experts"])
    return int(n_layers * (attention_matmul_params(hf)
                           + indexer_matmul_params(hf)
                           + routed * 3 * d * f + d * published))


def chosen_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs of one row that an indexer's choice keeps, ties
    aside: ``min(t + 1, topk)`` a query."""
    full = min(seq, topk)
    return full * (full + 1) // 2 + (seq - full) * topk


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Multiply-adds of the mixers' own products (no projection) for one
    token of a ``seq``-token sequence, forward, counted like a matrix's
    parameters (six operations each a step): the score and value products
    of the 32 heads over the CHOSEN pairs (the model's work, whatever the
    kernel walks), the indexer's 16 heads of 64 over the causal pairs, and
    the second ``Q K^T`` over the chosen pairs that the indexer's loss
    takes its target from, which runs forward alone and so counts a third."""
    sa = hf["sa_config"]
    heads = hf["num_attention_heads"] * hf["head_dim"]
    chosen = chosen_pairs(seq, sa["topk"]) / seq
    return n_layers * (
        heads * chosen * (2 + 1 / 3.0)
        + sa["indexer_num_heads"] * sa["indexer_head_dim"] * (seq + 1) / 2.0)


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step would read of one cached position were every
    position read: keys and values of every layer and the indexer's key (no
    cell serves this family; a served step reads ``topk`` positions' keys
    and values and every position's indexer key)."""
    return n_layers * itemsize * (
        2 * hf["num_key_value_heads"] * hf["head_dim"]
        + hf["sa_config"]["indexer_head_dim"])
