"""The multibyte_eva family: EvaByte's block as the release publishes it
(``config.json``, ``model_type`` ``evabyte``, ``attention_class`` ``eva``) and
``ray_tpu/models/llama.py`` trains it (``attn_kind="eva"``). The attention is
"Efficient Attention via Control Variates" (arXiv:2302.04542) in the form the
release computes it: steps 2 and 3 below are its DETERMINISTIC form of the
paper's chunk-level control variates (one learned pooling a chunk), not the
paper's sampled one. (The name sorts after ``moe.py``:
``tests/benchmark/test_benchmark_spec.py`` holds the sorted directory to
begin ``dense.py``, ``moe.py``.)

A layer, x the residual stream in float32 (``fp32_skip_add``), ``rms1p(x, w)
= x * rsqrt(mean x^2 + eps) * (1 + w)`` (``norm_add_unit_offset``), H heads of
width 128, c = ``chunk_size`` 16, w = ``window_size`` 2048:

1. ``h = rms1p(x)``; ``q, k, v = h Wq, h Wk, h Wv`` split into heads; RoPE
   (``rope_theta``, every dimension, absolute positions) on q and k;
2. summaries, a head a and a chunk j (positions c j .. c j + c - 1):
   ``alpha_m = softmax_m(phi_a . k_m)`` over the chunk's c positions,
   ``k~_j = mu_a + sum_m alpha_m k_m``, ``v~_j = sum_m alpha_m v_m``;
3. a query at t, ``W(t) = t // w``, sees ``{k_m : W(m) = W(t), m <= t}`` and
   ``{k~_j : (c j) // w < W(t)}`` (its own window's chunks are NOT summarised
   for it); one softmax over both at ``1/sqrt(128)``; ``Wo``; ``x += that``;
4. ``x += (silu(h' Wg) * (h' Wu)) Wd``, ``h' = rms1p(x)``;
5. after the last layer ``rms1p``, then a head ``[d, n V]`` (n =
   ``num_pred_heads``): columns ``i V .. (i + 1) V`` predict the byte at
   ``t + 1 + i``. The loss is the mean over the n heads of the mean
   cross-entropy over the positions whose target lies inside the row.

The reference below is those five steps in ``jax.numpy``, float32, every
product at ``highest``, the mask of step 3 built from positions a block of
queries at a time; it imports nothing of ``ray_tpu`` and owes nothing to
``ray_tpu/ops/eva.py``. Departures are set out in the configuration file's
``assumed``. Importing this file imports neither JAX nor the program; its
functions do.
"""

import functools
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import spec


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program has no ``eva``
    kind of mixer (``ray_tpu/models/llama.py`` before PR 52): called by the
    cell's new readers as the parent process loads them, so that a checkout
    that cannot train the cell fails in seconds, before it starts a trainer.
    Reads the source and imports nothing of JAX."""
    import os
    import re

    import ray_tpu

    path = os.path.join(os.path.dirname(ray_tpu.__file__), "models", "llama.py")
    with open(path) as f:
        if not re.search(r"^def eva_half\(", f.read(), re.M):
            raise spec.SpecError(
                f"family multibyte_eva needs the mixer kind 'eva', which "
                f"{path} does not have: this checkout's program cannot run it")


# ---- the program's config and weights ----------------------------------------

def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    hf = cfg_file["config"]
    if hf["attention_class"] != "eva" or hf["num_key_value_heads"] \
            != hf["num_attention_heads"]:
        raise spec.SpecError("family multibyte_eva: attention_class is 'eva' "
                             "and every head has its own keys")
    return llama.LlamaConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk,
        attn_kind="eva", eva_window=hf["window_size"],
        eva_chunk=hf["chunk_size"],
        norm_unit_offset=bool(hf["norm_add_unit_offset"]),
        residual_f32=bool(hf["fp32_skip_add"]),
        n_pred_heads=hf["num_pred_heads"])


def init_params(rng, cfg):
    from ray_tpu.models import llama

    return llama.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

QUERY_BLOCK = 256  # rows of scores at once: 32 heads x 16384 x 17408 float32 is 36 GB
STATIC_KEYS = ("rms_norm_eps", "rope_theta", "num_attention_heads",
               "chunk_size", "window_size", "num_pred_heads", "vocab_size")


def _static(cfg_file: Dict[str, Any]) -> Tuple:
    """What a compiled layer reads of the configuration, hashable."""
    return tuple((key, cfg_file["config"][key]) for key in STATIC_KEYS)


def _rms1p(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def _pooled(k, v, phi, mu, c: int):
    """Step 2 on k, v [b, s, H, d], phi, mu [H, d]: (k~, v~) [b, s / c, H, d]."""
    import jax
    import jax.numpy as jnp

    b, s, H, d = k.shape
    if s % c:
        raise spec.SpecError(f"{s} positions are not whole chunks of {c}")
    kc, vc = k.reshape(b, s // c, c, H, d), v.reshape(b, s // c, c, H, d)
    alpha = jax.nn.softmax(jnp.einsum("bjmhd,hd->bjmh", kc, phi), axis=2)
    return (mu + jnp.einsum("bjmh,bjmhd->bjhd", alpha, kc),
            jnp.einsum("bjmh,bjmhd->bjhd", alpha, vc))


def _eva(x, layer, hf: Dict[str, Any]):
    """Steps 1 to 3, residual included, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = x.shape
    H, c, w = hf["num_attention_heads"], hf["chunk_size"], hf["window_size"]
    h = _rms1p(x, layer["attn_norm"], hf["rms_norm_eps"])
    q = ref.rope((h @ layer["wq"]).reshape(b, s, H, -1), hf["rope_theta"])
    k = ref.rope((h @ layer["wk"]).reshape(b, s, H, -1), hf["rope_theta"])
    v = (h @ layer["wv"]).reshape(b, s, H, -1)
    width = q.shape[-1]
    k_pooled, v_pooled = _pooled(k, v, layer["eva_phi"], layer["eva_mu"], c)
    keys = jnp.concatenate([k, k_pooled], axis=1)       # [b, s + s / c, H, d]
    values = jnp.concatenate([v, v_pooled], axis=1)
    at = jnp.arange(s)                                  # a key's position
    chunk_at = c * jnp.arange(s // c)                   # a chunk's first
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def rows(args):  # queries [b, block, H, d] from position ``first``
        qb, first = args
        t = (first + jnp.arange(block))[:, None]
        own = (at[None, :] // w == t // w) & (at[None, :] <= t)
        earlier = chunk_at[None, :] // w < t // w
        seen = jnp.concatenate([own, earlier], axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, keys) / jnp.sqrt(
            ref.F32(width))
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, values)

    qs = q.reshape(b, s // block, block, H, width).swapaxes(0, 1)
    out = jax.lax.map(rows, (qs, jnp.arange(0, s, block)))
    return x + out.swapaxes(0, 1).reshape(b, s, H * width) @ layer["wo"]


def _block(x, layer, hf: Dict[str, Any]):
    from benchmark.lib import reference as ref

    b, s, d = x.shape
    x = _eva(x, layer, hf)
    h = _rms1p(x, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
    f = ref.in_chunks(functools.partial(
        ref.swiglu, gate=layer["w_gate"], up=layer["w_up"],
        down=layer["w_down"]), h)
    return x + f.reshape(b, s, d)


_layer = None


def _layer_fn():
    """The compiled layer, built on first use (importing this file imports
    no JAX)."""
    import jax

    @functools.partial(jax.jit, static_argnames=("static",))
    def layer_fn(x, layers, index, *, static):
        with jax.default_matmul_precision("highest"):
            layer = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, index, 0, False).astype(jax.numpy.float32), layers)
            return _block(x, layer, dict(static))

    return layer_fn


def hidden(params, tokens, cfg_file: Dict[str, Any], round_to=None):
    """tokens [b, s] -> final-norm hidden [b, s, d] float32, over as many
    layers as ``params`` holds. ``round_to`` a dtype: every weight and the
    residual stream after every layer pass through it, which is this
    reference computed in that precision (the loss limit's control)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    global _layer
    if _layer is None:
        _layer = _layer_fn()
    static = _static(cfg_file)
    if round_to is not None:
        params = jax.tree.map(lambda a: a.astype(round_to), params)
    x = params["embed"][tokens].astype(ref.F32)
    n_layers = params["layers"]["attn_norm"].shape[0]
    for i in range(n_layers):
        x = _layer(x, params["layers"], jnp.int32(i), static=static)
        if round_to is not None:
            x = x.astype(round_to).astype(ref.F32)
    return _rms1p(x, params["final_norm"].astype(ref.F32),
                  cfg_file["config"]["rms_norm_eps"])


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, n V]: head i's are columns i V .. (i + 1) V."""
    from benchmark.lib import reference as ref

    return ref._project(hidden(params, tokens, cfg_file), params["lm_head"])


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's, by the next byte's head (no cell serves the
    family: a served step would take up to n bytes from the n heads)."""
    from benchmark.lib import reference as ref

    V = cfg_file["config"]["vocab_size"]
    x = hidden(params, tokens, cfg_file)
    return ref._margins(x[0], params["lm_head"][:, :V], following)


def _heads_nll(x, tokens, head, n: int, V: int):
    """Step 5's loss of hidden x [b, s, d] and tokens [b, s + 1]."""
    import jax
    import jax.numpy as jnp

    b, s, _ = x.shape
    with jax.default_matmul_precision("highest"):
        out = (x @ head.astype(jnp.float32)).reshape(b, s, n, V)
        each = []
        for i in range(n):  # head i at t predicts tokens[t + 1 + i]
            logp = jax.nn.log_softmax(out[:, :s - i, i], axis=-1)
            target = tokens[:, 1 + i:]
            each.append(-jnp.take_along_axis(
                logp, target[..., None], axis=-1).mean())
        return jnp.stack(each).mean()


_nll = None


def loss(params, tokens, cfg_file: Dict[str, Any], round_to=None):
    """The mean over the prediction heads of each one's mean cross entropy of
    tokens [b, s + 1]. ``round_to``: as ``hidden``'s."""
    import jax
    import jax.numpy as jnp

    hf = cfg_file["config"]
    x = hidden(params, tokens[:, :-1], cfg_file, round_to=round_to)
    head = params["lm_head"]
    if round_to is not None:
        head = head.astype(round_to)
    global _nll
    if _nll is None:
        _nll = jax.jit(_heads_nll, static_argnums=(3, 4))
    ce = _nll(x, tokens, head, hf["num_pred_heads"], hf["vocab_size"])
    return {"loss": ce, "ce": ce, "aux": jnp.float32(0)}


def loss_and_grads(params, tokens, cfg_file: Dict[str, Any]):
    """(loss, gradients of every parameter) of the reference, by
    ``jax.grad`` through it, on float32 copies of ``params``."""
    import jax
    import jax.numpy as jnp

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return jax.value_and_grad(
        lambda p: loss(p, tokens, cfg_file)["loss"])(params)


# ---- the arithmetic ------------------------------------------------------------

def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms, ``phi`` and
    ``mu`` left out), and the head's columns beyond the first ``vocab_size``:
    the harness counts one head of ``hidden_size x vocab_size`` itself
    (``arithmetic.train_flops_per_token``), the other ``num_pred_heads - 1``
    are this family's."""
    d, f = hf["hidden_size"], hf["intermediate_size"]
    width = hf["num_attention_heads"] * (d // hf["num_attention_heads"])
    return int(n_layers * (4 * d * width + 3 * d * f)
               + (hf["num_pred_heads"] - 1) * d * hf["vocab_size"])


def visible_pairs(seq: int, window: int, chunk: int) -> Tuple[int, int]:
    """(query, key) pairs of one head that step 3 leaves visible in a
    ``seq``-position row: inside the windows (each one's causal half), and
    query against summary (window i, from 0, sees the ``i window / chunk``
    summaries of the windows before it)."""
    whole, rest = divmod(seq, window)
    local = whole * window * (window + 1) // 2 + rest * (rest + 1) // 2
    summary = (window // chunk) * (window * whole * (whole - 1) // 2
                                   + rest * whole)
    return local, summary


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Multiply-adds of the mixers' own products (no projection) for one
    token of a ``seq``-token sequence, forward, counted like a matrix's
    parameters (6 operations each forward and backward): the VISIBLE pairs
    alone, a score and a value product of the head width each, and the
    pooling's two weighted sums (2 x chunk x width a chunk and head). A pair
    a tile computes and masks, the pooling's logits and softmaxes are not
    counted: ``mfu`` reads under what the step did, never past it."""
    width = hf["hidden_size"] // hf["num_attention_heads"]
    local, summary = visible_pairs(seq, hf["window_size"], hf["chunk_size"])
    return n_layers * hf["num_attention_heads"] * width * (
        2.0 * (local + summary) / seq + 2.0)


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step would read of one position of the context before
    its window: that position's sixteenth of a chunk's summary, a key and a
    value a head and layer. (Beside it the step reads its own window's
    buffer, up to ``window_size`` positions of keys and values whole, which
    does not grow with the context.) No cell serves the family: the served
    form is dark (``PERF.md`` section 7)."""
    d = hf["hidden_size"]
    return n_layers * 2 * d * itemsize // hf["chunk_size"]
