"""The plain float32 reference: Mistral-style dense and Mixtral-style sparse
decoder blocks in straightforward ``jax.numpy``.

No cache, no kernel, no batching tricks, and nothing of ``ray_tpu.models``:
only the program's parameter tree is taken, because the same weights have to
go through both. Weights are upcast one layer at a time, and every matrix
product runs at ``highest`` precision (on a TPU a float32 product is bf16
passes otherwise).

Follows the published descriptions (mistral-inference and the Mixtral paper):
pre-norm RMSNorm, rotary embedding on interleaved pairs, grouped-query causal
attention, SwiGLU; with experts, a softmax router whose top-k gates are
renormalised. Two departures, both the program's training path and both set
out in the configuration file's ``assumed``: tokens beyond an expert's
capacity are dropped (queue order: every token's first choice, then every
token's second), and the balancing loss counts first choices only.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
F32 = jnp.float32


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [b, s, h, hd]: rotate the pairs (x[2i], x[2i+1]) by pos * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def _attention(x: jax.Array, layer: Params, hf: Dict[str, Any]) -> jax.Array:
    b, s, _ = x.shape
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = layer["wq"].shape[-1] // hq
    h = _rms(x, layer["attn_norm"], hf["rms_norm_eps"])
    q = _rope((h @ layer["wq"]).reshape(b, s, hq, hd), hf["rope_theta"])
    k = _rope((h @ layer["wk"]).reshape(b, s, hkv, hd), hf["rope_theta"])
    v = (h @ layer["wv"]).reshape(b, s, hkv, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(qkv):  # one key/value head and the query heads that share it
        qg, kg, vg = qkv  # [b, s, hq/hkv, hd], [b, s, hd], [b, s, hd]
        scores = jnp.einsum("bqgd,bkd->bgqk", qg, kg) / jnp.sqrt(F32(hd))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", p, vg)

    qg = q.reshape(b, s, hkv, hq // hkv, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(group, (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, s, hq * hd)
    return x + out @ layer["wo"]


def _swiglu(h: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array
            ) -> jax.Array:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _in_chunks(fn, tokens: jax.Array, chunk: int = 4096) -> jax.Array:
    """fn over [G, d] in pieces, so that [G, d_ff] never exists whole."""
    g = tokens.shape[0]
    if g <= chunk or g % chunk:
        return fn(tokens)
    return jax.lax.map(fn, tokens.reshape(g // chunk, chunk, -1)).reshape(g, -1)


def _experts(x: jax.Array, layer: Params, hf: Dict[str, Any],
             capacity_factor: Optional[float]) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    n_exp, top_k = hf["num_local_experts"], hf["num_experts_per_tok"]
    h = _rms(x, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
    probs = jax.nn.softmax(h @ layer["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-9)
    chosen = jax.nn.one_hot(top_i, n_exp, dtype=jnp.int32)  # [G, K, E]
    if capacity_factor is not None:
        # a token's place in its expert's queue: all first choices in token
        # order, then all second choices; places beyond the capacity drop
        g = b * s
        cap = max(1, int(capacity_factor * g * top_k / n_exp))
        order = chosen.transpose(1, 0, 2).reshape(top_k * g, n_exp)
        place = (jnp.cumsum(order, axis=0) - order).reshape(top_k, g, n_exp)
        place = (place.transpose(1, 0, 2) * chosen).sum(-1)  # [G, K]
        top_p = top_p * (place < cap)
    weight = jnp.einsum("gk,gke->ge", top_p, chosen.astype(F32))
    y = jnp.zeros_like(h)
    for e in range(n_exp):
        one = functools.partial(_swiglu, gate=layer["e_gate"][e],
                                up=layer["e_up"][e], down=layer["e_down"][e])
        y = y + weight[:, e:e + 1] * _in_chunks(one, h)
    first = jnp.mean(chosen[:, 0, :].astype(F32), axis=0)
    aux = n_exp * jnp.sum(first * jnp.mean(probs, axis=0))
    return x + y.reshape(b, s, d), aux


@functools.partial(jax.jit, static_argnames=("hf_items", "capacity_factor"))
def _layer(x, layers, index, *, hf_items, capacity_factor):
    hf = dict(hf_items)
    with jax.default_matmul_precision("highest"):
        layer = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, False).astype(F32),
            layers)
        x = _attention(x, layer, hf)
        if "router" in layer:
            return _experts(x, layer, hf, capacity_factor)
        h = _rms(x, layer["mlp_norm"], hf["rms_norm_eps"])
        return x + _swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]), F32(0)


def _static(hf: Dict[str, Any]) -> Tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta", "num_local_experts", "num_experts_per_tok")
    return tuple((k, hf[k]) for k in keys if k in hf)


def hidden(params: Params, tokens: jax.Array, hf: Dict[str, Any],
           capacity_factor: Optional[float] = None
           ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s] -> (final-norm hidden [b, s, d] float32, mean balancing
    loss over the layers). ``capacity_factor`` None routes without dropping."""
    x = params["embed"][tokens].astype(F32)
    n_layers = params["layers"]["wq"].shape[0]
    aux = F32(0)
    for i in range(n_layers):
        x, a = _layer(x, params["layers"], jnp.int32(i), hf_items=_static(hf),
                      capacity_factor=capacity_factor)
        aux = aux + a
    x = _rms(x, params["final_norm"].astype(F32), hf["rms_norm_eps"])
    return x, aux / n_layers


def _head(params: Params) -> jax.Array:
    return (params["lm_head"] if "lm_head" in params else params["embed"].T)


@jax.jit
def _project(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32)


def logits(params: Params, tokens: jax.Array, hf: Dict[str, Any]) -> jax.Array:
    """Float32 logits [b, s, V], routing without drops."""
    x, _ = hidden(params, tokens, hf)
    return _project(x, _head(params))


@jax.jit
def _margins(x, head, following):
    with jax.default_matmul_precision("highest"):
        out = x @ head.astype(F32)  # [s, V]
        took = jnp.take_along_axis(out, following[:, None], axis=-1)[:, 0]
        return {"margin": out.max(-1) - took, "scale": jnp.abs(out).max(-1),
                "finite": jnp.isfinite(out).all(-1)}


def token_margins(params: Params, tokens: jax.Array, following: jax.Array,
                  hf: Dict[str, Any]) -> Dict[str, jax.Array]:
    """For one sequence ``tokens`` [1, s] and the token that followed each
    position, ``following`` [s]: how far that token's logit lies under the
    position's best (0 where it is the argmax), the logits' largest
    magnitude there, and whether they are finite. One program whatever the
    answer's length; the caller reads the rows it has answers for."""
    x, _ = hidden(params, tokens, hf)
    return _margins(x[0], _head(params), following)


@jax.jit
def _sequence_nll(x, targets, head):
    with jax.default_matmul_precision("highest"):
        def one(args):  # a sequence at a time: [s, V] float32, not [b, s, V]
            xs, ts = args
            logp = jax.nn.log_softmax(xs @ head.astype(F32), axis=-1)
            return -jnp.take_along_axis(logp, ts[:, None], axis=-1)[:, 0].sum()
        return jax.lax.map(one, (x, targets)).sum() / targets.size


def loss(params: Params, tokens: jax.Array, hf: Dict[str, Any],
         capacity_factor: Optional[float] = None) -> Dict[str, jax.Array]:
    """Next-token cross entropy of tokens [b, s+1], and with experts the
    balancing loss weighted as the published config says."""
    x, aux = hidden(params, tokens[:, :-1], hf, capacity_factor)
    ce = _sequence_nll(x, tokens[:, 1:], _head(params))
    total = ce + hf.get("router_aux_loss_coef", 0.0) * aux \
        if "num_local_experts" in hf else ce
    return {"loss": total, "ce": ce, "aux": aux}
