"""The afmoe family: Trinity's block as arcee-ai publish it (``config.json``,
``model_type`` ``afmoe``, and the family's modeling file) and
``ray_tpu/models/moe.py`` trains it in its patterned form. (The file is named
after the model too because ``tests/benchmark/test_benchmark_spec.py`` holds
the sorted directory to begin ``dense.py``, ``moe.py``: a family's name has
to sort after those.)

A layer, x the residual stream (float32 here, everything at ``highest``):

- attention half: ``h = rms(x, attn_norm)``; q, k, v projections into 48 / 8
  / 8 heads of 128; ``rms`` over each head's 128 of q and of k (gains
  ``q_norm``, ``k_norm`` [128]); in a ``sliding_attention`` layer q and k are
  rotated and a query at p sees the keys in (p - window, p]; in a
  ``full_attention`` layer nothing is rotated and the mask is causal; scale
  1/sqrt(128); the heads' output times ``sigmoid(h @ wg)``; ``x + rms(a @ wo,
  attn_post_norm)``;
- feed-forward half: ``h = rms(x, mlp_norm)``; a dense layer is a SwiGLU of
  ``intermediate_size``; an expert layer scores ``s = sigmoid(h @ router)``
  over all published experts in float32, chooses the top K of ``s +
  router_bias``, weighs them ``s[sel] / sum(s[sel]) * route_scale`` and adds
  the shared expert; ``x + rms(f, mlp_post_norm)``;
- the embedding is times sqrt(hidden) (``mup_enabled``), the head untied.

The chip's share: ``config`` in the configuration's file holds the keys as
they are run (``num_experts`` held here, the first ones; ``vocab_size`` the
slice) and ``num_experts_published`` beside them. The router keeps the
published width; an assignment to an expert that lives elsewhere adds
nothing; capacity is reckoned from the published count and rows beyond it
drop in queue order (every token's first choice, then every token's second,
...), as the program's buffers drop them. The layers run are those
``layers_run`` names by their published index: the first leading dense layer
and then a whole period from a period boundary.

Departures are set out in the configuration file's ``assumed``. Importing
this file imports neither JAX nor the program; its functions do, and the
reference (``hidden``, ``logits``, ``loss``, ``token_margins``) imports
nothing of ``ray_tpu``.
"""

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import spec

# ---- the program's config and weights ----------------------------------------

# what the program's config classes must have for this family, by the file
# that defines each
NEEDS = {"moe": "n_experts_held", "llama": "attn_gate"}

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program cannot build
    this family's config (``ray_tpu/models`` before PR 43 has neither
    field), as ``olmoe.require_program`` does: called by the cell's new
    readers as the parent process loads them, so that a checkout that cannot
    train the cell fails in seconds, before it starts a trainer. Reads the
    source and imports nothing of JAX."""
    import os
    import re

    import ray_tpu

    models = os.path.join(os.path.dirname(ray_tpu.__file__), "models")
    for module, field in NEEDS.items():
        with open(os.path.join(models, module + ".py")) as f:
            if not re.search(rf"^\s+{field}\s*:", f.read(), re.M):
                raise spec.SpecError(
                    f"family trinity_afmoe needs the config field {field!r}, which "
                    f"{models}/{module}.py does not have: this checkout's "
                    f"program cannot run it")


def layers_run(hf: Dict[str, Any], n_layers: int) -> List[int]:
    """The published indices of the first ``n_layers`` layers run: those
    ``layers_run`` names where the configuration is cut in depth (one
    leading dense layer, then whole periods from a period boundary), else
    the published order."""
    run = list(hf.get("layers_run") or range(hf["num_hidden_layers"]))
    if n_layers > len(run):
        raise spec.SpecError(f"{n_layers} layers asked of {len(run)}")
    return run[:n_layers]


def kinds(hf: Dict[str, Any], n_layers: int) -> Tuple[str, ...]:
    """The program's kind of each layer run, from ``layer_types``."""
    return tuple(KINDS[hf["layer_types"][i]] for i in layers_run(hf, n_layers))


def dense_layers_run(hf: Dict[str, Any], n_layers: int) -> int:
    return sum(i < hf["num_dense_layers"] for i in layers_run(hf, n_layers))


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import moe

    hf, assumed = cfg_file["config"], cfg_file["assumed"]
    return moe.MoEConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], attn_head_dim=hf["head_dim"],
        d_ff=hf["moe_intermediate_size"], d_ff_dense=hf["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk,
        qk_norm_head=True, attn_gate=True, sandwich_norm=True,
        embedding_multiplier=(math.sqrt(hf["hidden_size"])
                              if hf["mup_enabled"] else 1.0),
        layer_kinds=kinds(hf, n_layers),
        n_dense_layers=dense_layers_run(hf, n_layers),
        sliding_window=hf["sliding_window"],
        n_experts=hf.get("num_experts_published", hf["num_experts"]),
        n_experts_held=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        n_shared_experts=hf["num_shared_experts"],
        router_score=hf["score_func"], router_bias=True,
        norm_topk_prob=bool(hf["route_norm"]),
        route_scale=float(hf["route_scale"]), balance="sequence",
        router_aux_coef=float(hf["load_balance_coeff"]),
        capacity_factor=float(assumed["capacity_factor"]))


def init_params(rng, cfg):
    from ray_tpu.models import moe

    return moe.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

_STATIC_KEYS = (
    "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
    "rope_theta", "sliding_window", "num_experts", "num_experts_per_tok",
    "route_norm", "route_scale", "score_func", "hidden_size", "mup_enabled",
    "load_balance_coeff")

QUERY_BLOCK = 512  # rows of scores at once: 48 heads x 8192^2 float32 is 12.9 GB


def _static(cfg_file: Dict[str, Any], capacity_factor: Optional[float]) -> Tuple:
    hf = cfg_file["config"]
    return tuple((k, hf[k]) for k in _STATIC_KEYS) + (
        ("num_experts_published", hf.get("num_experts_published",
                                         hf["num_experts"])),
        ("capacity_factor", capacity_factor))


def _attention(x, layer, hf: Dict[str, Any], kind: str):
    """The attention half, residual included, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = x.shape
    hq, hkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    eps = hf["rms_norm_eps"]
    h = ref.rms(x, layer["attn_norm"], eps)
    q = ref.rms((h @ layer["wq"]).reshape(b, s, hq, hd), layer["q_norm"], eps)
    k = ref.rms((h @ layer["wk"]).reshape(b, s, hkv, hd), layer["k_norm"], eps)
    v = (h @ layer["wv"]).reshape(b, s, hkv, hd)
    if kind == "window":
        q, k = ref.rope(q, hf["rope_theta"]), ref.rope(k, hf["rope_theta"])
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    kpos = jnp.arange(s)

    def rows(args):  # queries [b, block, hkv, g, hd] starting at ``first``
        qb, first = args
        qpos = first + jnp.arange(block)
        seen = kpos[None, :] <= qpos[:, None]
        if kind == "window":
            seen = seen & (qpos[:, None] - kpos[None, :] < hf["sliding_window"])
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k) / jnp.sqrt(ref.F32(hd))
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v)

    qs = q.reshape(b, s // block, block, hkv, hq // hkv, hd).swapaxes(0, 1)
    out = jax.lax.map(rows, (qs, jnp.arange(0, s, block)))
    out = out.swapaxes(0, 1).reshape(b, s, hq * hd)
    out = out * jax.nn.sigmoid(h @ layer["wg"])
    return x + ref.rms(out @ layer["wo"], layer["attn_post_norm"], eps)


def _experts(h, layer, hf: Dict[str, Any], b: int):
    """h [G, d], the normed input -> (the routed and shared experts' sum
    [G, d], the balancing term)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    g = h.shape[0]
    e_all, held, top_k = (hf["num_experts_published"], hf["num_experts"],
                          hf["num_experts_per_tok"])
    logits = h @ layer["router"]
    scores = (jax.nn.sigmoid(logits) if hf["score_func"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    _, top_i = jax.lax.top_k(scores + layer["router_bias"], top_k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if hf["route_norm"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    top_s = top_s * hf["route_scale"]
    chosen = jax.nn.one_hot(top_i, e_all, dtype=jnp.int32)  # [G, K, E]
    if hf["capacity_factor"] is not None:
        # a token's place in its expert's queue: all first choices in token
        # order, then all second choices; places beyond the capacity drop
        cap = max(1, int(hf["capacity_factor"] * g * top_k / e_all))
        order = chosen.transpose(1, 0, 2).reshape(top_k * g, e_all)
        place = (jnp.cumsum(order, axis=0) - order).reshape(top_k, g, e_all)
        place = (place.transpose(1, 0, 2) * chosen).sum(-1)  # [G, K]
        top_s = top_s * (place < cap)
    weight = jnp.einsum("gk,gke->ge", top_s, chosen.astype(ref.F32))
    y = ref.in_chunks(functools.partial(
        ref.swiglu, gate=layer["s_gate"], up=layer["s_up"],
        down=layer["s_down"]), h)
    for e in range(held):  # the experts that live elsewhere add nothing here
        one = functools.partial(ref.swiglu, gate=layer["e_gate"][e],
                                up=layer["e_up"][e], down=layer["e_down"][e])
        y = y + weight[:, e:e + 1] * ref.in_chunks(one, h)
    # a sequence at a time, over all K choices and all published experts
    share = chosen.sum(1).astype(ref.F32).reshape(b, -1, e_all).mean(1)
    mass = (scores / scores.sum(-1, keepdims=True)).reshape(b, -1, e_all).mean(1)
    return y, (e_all / top_k) * jnp.mean(jnp.sum(share * mass, axis=-1))


def _block(x, layer, hf: Dict[str, Any], kind: str, dense: bool):
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, d = x.shape
    eps = hf["rms_norm_eps"]
    x = _attention(x, layer, hf, kind)
    h = ref.rms(x, layer["mlp_norm"], eps).reshape(b * s, d)
    if dense:
        f, aux = ref.in_chunks(functools.partial(
            ref.swiglu, gate=layer["w_gate"], up=layer["w_up"],
            down=layer["w_down"]), h), jnp.float32(0)
    else:
        f, aux = _experts(h, layer, hf, b)
    return x + ref.rms(f.reshape(b, s, d), layer["mlp_post_norm"], eps), aux


def _layer_fn():
    """The compiled layer, built on first use (importing this file imports
    no JAX)."""
    import jax

    @functools.partial(jax.jit, static_argnames=("kind", "dense", "static"))
    def layer_fn(x, layers, index, *, kind, dense, static):
        with jax.default_matmul_precision("highest"):
            layer = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, index, 0, False).astype(jax.numpy.float32), layers)
            return _block(x, layer, dict(static), kind, dense)

    return layer_fn


_layer = None


def hidden(params, tokens, cfg_file: Dict[str, Any],
           capacity_factor: Optional[float] = None,
           window: bool = True, round_to=None):
    """tokens [b, s] -> (final-norm hidden [b, s, d] float32, mean of the
    expert layers' balancing terms), over as many layers as ``params``
    holds. Two controls, for the checks of the checks
    (``tests/benchmark/afmoe_chip_check.py``): ``window=False``, every
    sliding layer sees its whole past (still rotated), which is what a
    program that forgot the band would compute; ``round_to`` a dtype, every
    weight and the residual stream after every layer pass through it, which
    is this reference computed in that precision."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    global _layer
    if _layer is None:
        _layer = _layer_fn()
    hf = cfg_file["config"]
    static = _static(cfg_file, capacity_factor)
    if not window:
        static = tuple((k, 1 << 30 if k == "sliding_window" else v)
                       for k, v in static)
    n_dense = (jax.tree.leaves(params["dense_layers"])[0].shape[0]
               if "dense_layers" in params else 0)
    n_sparse = jax.tree.leaves(params["layers"])[0].shape[0]
    run = kinds(hf, n_dense + n_sparse)
    if round_to is not None:
        params = jax.tree.map(lambda a: a.astype(round_to), params)
    x = params["embed"][tokens].astype(ref.F32)
    if hf["mup_enabled"]:
        x = x * jnp.sqrt(ref.F32(hf["hidden_size"]))
    aux = ref.F32(0)
    for i, kind in enumerate(run):
        dense = i < n_dense
        x, a = _layer(x, params["dense_layers" if dense else "layers"],
                      jnp.int32(i if dense else i - n_dense), kind=kind,
                      dense=dense, static=static)
        if round_to is not None:
            x = x.astype(round_to).astype(ref.F32)
        aux = aux + a
    x = ref.rms(x, params["final_norm"].astype(ref.F32), hf["rms_norm_eps"])
    return x, aux / max(1, n_sparse)


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V] over the slice, routing without drops."""
    from benchmark.lib import reference as ref

    x, _ = hidden(params, tokens, cfg_file)
    return ref._project(x, params["lm_head"])


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's, routing without drops."""
    from benchmark.lib import reference as ref

    x, _ = hidden(params, tokens, cfg_file)
    return ref._margins(x[0], params["lm_head"], following)


def loss(params, tokens, cfg_file: Dict[str, Any], round_to=None):
    """Next-token cross entropy of tokens [b, s+1] over the slice under the
    capacity that ``assumed`` sets, and the balancing term weighted by the
    published ``load_balance_coeff``. ``round_to``: as ``hidden``'s."""
    from benchmark.lib import reference as ref

    x, aux = hidden(params, tokens[:, :-1], cfg_file,
                    cfg_file["assumed"].get("capacity_factor"),
                    round_to=round_to)
    head = params["lm_head"]
    if round_to is not None:
        head = head.astype(round_to)
    ce = ref._sequence_nll(x, tokens[:, 1:], head)
    coef = cfg_file["config"]["load_balance_coeff"]
    return {"loss": ce + coef * aux, "ce": ce, "aux": aux}


# ---- the arithmetic ------------------------------------------------------------

def _attention_matmul_params(hf: Dict[str, Any]) -> int:
    """One layer's five attention matrices: q, k, v, the gate and o."""
    d = hf["hidden_size"]
    q = hf["num_attention_heads"] * hf["head_dim"]
    return 3 * d * q + 2 * d * hf["num_key_value_heads"] * hf["head_dim"]


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms and the
    selection bias left out). An expert layer: the shared expert, the router
    at its published width and the routed experts held here, or
    (``active_only``) the visits a token pays them on average:
    ``num_experts_per_tok * held / published`` experts' worth."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    published = hf.get("num_experts_published", hf["num_experts"])
    routed = (hf["num_experts_per_tok"] * hf["num_experts"] / published
              if active_only else hf["num_experts"])
    dense = dense_layers_run(hf, n_layers)
    sparse = (hf["num_shared_experts"] + routed) * 3 * d * f + d * published
    return int(n_layers * _attention_matmul_params(hf)
               + dense * 3 * d * hf["intermediate_size"]
               + (n_layers - dense) * sparse)


def mean_keys(seq: int, window: Optional[int]) -> float:
    """Keys a query sees, mean over a ``seq``-token causal sequence, at half
    the square as the dense family counts it, less the triangle below the
    band."""
    w = min(window or seq, seq)
    return w - w * w / (2.0 * seq)


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Multiply-adds of the score and value products for one token of a
    ``seq``-token sequence, forward, each layer by its kind: counted like a
    matrix's parameters, 6 operations each forward and backward."""
    heads = hf["num_attention_heads"] * hf["head_dim"]
    return sum(2 * heads * mean_keys(
        seq, hf["sliding_window"] if kind == "window" else None)
        for kind in kinds(hf, n_layers))


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step would read of one cached position: keys and
    values of every layer (no cell serves this family)."""
    return 2 * n_layers * hf["num_key_value_heads"] * hf["head_dim"] * itemsize
