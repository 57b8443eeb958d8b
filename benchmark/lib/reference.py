"""The plain float32 reference's shared pieces, in straightforward
``jax.numpy``: what a family (``benchmark/families/<family>.py``) builds its
block from, and the loop, head, margins and loss round any block.

No cache, no kernel, no batching tricks, and nothing of ``ray_tpu.models``:
only the program's parameter tree is taken, because the same weights have to
go through both. Weights are upcast one layer at a time, and every matrix
product runs at ``highest`` precision (on a TPU a float32 product is bf16
passes otherwise).

Follows the published descriptions (mistral-inference): pre-norm RMSNorm,
rotary embedding on interleaved pairs, grouped-query causal attention,
SwiGLU. A family's ``block(x, layer, hf) -> (x, aux)`` takes one layer's
float32 weights and ``hf``, the keys and values the family itself chose
(``static``: a tuple of items, hashable, so that it can key the compiled
layer); the pieces here read from ``hf`` only the keys their docstrings name.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]
Static = Tuple[Tuple[str, Any], ...]
Block = Callable[[jax.Array, Params, Dict[str, Any]], Tuple[jax.Array, jax.Array]]
F32 = jnp.float32


def rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x [b, s, h, hd]: rotate the pairs (x[2i], x[2i+1]) by pos * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention(x: jax.Array, layer: Params, hf: Dict[str, Any]) -> jax.Array:
    """The attention half of a pre-norm block, residual included. Reads
    ``num_attention_heads``, ``num_key_value_heads``, ``rms_norm_eps`` and
    ``rope_theta`` of ``hf``; the head size is the projection's."""
    b, s, _ = x.shape
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = layer["wq"].shape[-1] // hq
    h = rms(x, layer["attn_norm"], hf["rms_norm_eps"])
    q = rope((h @ layer["wq"]).reshape(b, s, hq, hd), hf["rope_theta"])
    k = rope((h @ layer["wk"]).reshape(b, s, hkv, hd), hf["rope_theta"])
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


def swiglu(h: jax.Array, gate: jax.Array, up: jax.Array, down: jax.Array
            ) -> jax.Array:
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def in_chunks(fn, tokens: jax.Array, chunk: int = 4096) -> jax.Array:
    """fn over [G, d] in pieces, so that [G, d_ff] never exists whole."""
    g = tokens.shape[0]
    if g <= chunk or g % chunk:
        return fn(tokens)
    return jax.lax.map(fn, tokens.reshape(g // chunk, chunk, -1)).reshape(g, -1)


@functools.partial(jax.jit, static_argnames=("block", "static"))
def _layer(x, layers, index, *, block, static):
    with jax.default_matmul_precision("highest"):
        layer = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, index, 0, False).astype(F32),
            layers)
        return block(x, layer, dict(static))


def hidden(params: Params, tokens: jax.Array, block: Block, static: Static
           ) -> Tuple[jax.Array, jax.Array]:
    """tokens [b, s] -> (final-norm hidden [b, s, d] float32, mean of the
    blocks' ``aux`` over the layers), for a stack of like layers under
    ``params["layers"]``. Reads ``rms_norm_eps`` of ``static``."""
    x = params["embed"][tokens].astype(F32)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    aux = F32(0)
    for i in range(n_layers):
        x, a = _layer(x, params["layers"], jnp.int32(i), block=block, static=static)
        aux = aux + a
    x = rms(x, params["final_norm"].astype(F32), dict(static)["rms_norm_eps"])
    return x, aux / n_layers


def _head(params: Params) -> jax.Array:
    return (params["lm_head"] if "lm_head" in params else params["embed"].T)


@jax.jit
def _project(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32)


def logits(params: Params, tokens: jax.Array, block: Block, static: Static
           ) -> jax.Array:
    """Float32 logits [b, s, V]."""
    x, _ = hidden(params, tokens, block, static)
    return _project(x, _head(params))


@jax.jit
def _margins(x, head, following):
    with jax.default_matmul_precision("highest"):
        out = x @ head.astype(F32)  # [s, V]
        took = jnp.take_along_axis(out, following[:, None], axis=-1)[:, 0]
        return {"margin": out.max(-1) - took, "scale": jnp.abs(out).max(-1),
                "finite": jnp.isfinite(out).all(-1)}


def token_margins(params: Params, tokens: jax.Array, following: jax.Array,
                  block: Block, static: Static) -> Dict[str, jax.Array]:
    """For one sequence ``tokens`` [1, s] and the token that followed each
    position, ``following`` [s]: how far that token's logit lies under the
    position's best (0 where it is the argmax), the logits' largest
    magnitude there, and whether they are finite. One program whatever the
    answer's length; the caller reads the rows it has answers for."""
    x, _ = hidden(params, tokens, block, static)
    return _margins(x[0], _head(params), following)


@jax.jit
def _sequence_nll(x, targets, head):
    with jax.default_matmul_precision("highest"):
        def one(args):  # a sequence at a time: [s, V] float32, not [b, s, V]
            xs, ts = args
            logp = jax.nn.log_softmax(xs @ head.astype(F32), axis=-1)
            return -jnp.take_along_axis(logp, ts[:, None], axis=-1)[:, 0].sum()
        return jax.lax.map(one, (x, targets)).sum() / targets.size


def loss(params: Params, tokens: jax.Array, block: Block, static: Static
         ) -> Dict[str, jax.Array]:
    """Next-token cross entropy ``ce`` of tokens [b, s+1] and the blocks'
    mean ``aux``; what a family's loss adds up from them is the family's."""
    x, aux = hidden(params, tokens[:, :-1], block, static)
    return {"ce": _sequence_nll(x, tokens[:, 1:], _head(params)), "aux": aux}
