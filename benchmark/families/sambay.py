"""The sambay family ("SambaY", a decoder-hybrid-decoder): Microsoft's
Phi-4-mini-flash-reasoning as its ``phi4flash`` modeling file computes it and
``ray_tpu/models/sambay.py`` serves it. Per token, ``x`` the residual stream,
``LN`` a LayerNorm with weight and bias, ``eps`` = ``layer_norm_eps``, no
rotation anywhere:

- ``x = E[token]``; every layer ``x += Mixer(LN(x))``, then ``x += W_down(silu(
  W_gate u) * (W_up u))`` with ``u = LN(x)``; ``logits = LN(x) E^T`` (tied);
- layer ``l`` of ``n``, by kind: even ``l <= n/2`` ``mamba``, even ``l > n/2``
  ``gmu``; odd ``l < n/2`` ``window``, ``l = n/2 + 1`` ``full``, odd ``l`` after it
  ``cross`` (``mb_per_layer`` 2; the second half reads layers ``n/2`` and ``n/2 + 1``);
- ``mamba`` (S6), ``u`` the normed input: ``[xs | z] = u W_in``; ``xs = silu(conv(xs)
  + b)``, causal, depthwise, ``d_conv`` wide; ``[delta | B | C] = xs W_x``; ``dt =
  softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} +
  (dt_t xs_t) B_t^T`` with ``h`` [d_inner, d_state], zero at the start; ``y_t = h_t
  C_t + D xs_t``; out ``= (y * silu(z)) W_out``. The LAST mamba layer's ``y`` is the
  memory ``m`` of the ``gmu`` layers;
- ``gmu``: out ``= (m * silu(u W_1)) W_2``;
- ``window`` / ``full`` / ``cross``: differential attention. ``q = u W_q + b_q`` [hq,
  hd]; ``k``, ``v`` [hkv, hd] the layer's own (``window``, ``full``) or the ``full``
  layer's (``cross``). Pair ``j < hq/2``: ``g = j // (hq/hkv)``; ``A_1 = softmax(q[2j]
  k[2g]^T scale + mask)``, ``A_2 = softmax(q[2j+1] k[2g+1]^T scale + mask)``, ``vv =
  [v[2g] | v[2g+1]]``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init(l)``,
  ``lam_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o_j = (1 - lam_init(l)) RMSNorm((A_1 - lam
  A_2) vv) * w_sub``; heads ``2j``, ``2j+1`` of the output are its halves; then ``W_o +
  b_o``. The mask is causal, and in a ``window`` layer also ``i - j < sliding_window``.

The reference below follows that in float32 ``jax.numpy`` at matmul precision
``highest``: the recurrence is a ``lax.scan`` over time with the state ``[d_inner,
d_state]`` as its carry, layers are taken one at a time in the published order,
attention is two explicit softmaxes a pair over the whole sequence, there is no
cache, no ring and no skipped layer (every layer runs over every token), and of
``ray_tpu.models`` only the parameter tree is taken (stacked by layer kind; the
projections that tree keeps ``[out, in]`` are used as they lie, ``A_log`` is kept
``[d_state, d_inner]`` and transposed here). Departures from the published code
are those the configuration file's ``assumed`` sets out: what the catalogued
``config.json`` does not hold (the head size, the softmax scale, the Mamba sizes,
which layer is of which kind, the differential heads, the projections' biases,
the state's type, the initialisation) is the modeling file's as its builder
remembers it, and is said to be so there. Importing this file imports neither JAX
nor the program; its functions do."""

import functools
import math
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import spec

# ---- the program's config and weights ----------------------------------------

# what the checkout's program must have for this family: the module and, in
# it, the config's fields
NEEDS = {"sambay": ("sliding_window", "mamba_dt_rank"),
         "llama": ("attn_scale",)}
KINDS = ("mamba", "window", "full", "gmu", "cross")


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program cannot build this
    family's config (``ray_tpu/models`` before PR 34 has no ``sambay.py``). The
    cell's readers call it as the parent process loads them, before a replica
    is deployed (``ssm_hybrid.require_program`` says why). Reads the source and
    imports nothing."""
    import os
    import re

    import ray_tpu

    models = os.path.join(os.path.dirname(ray_tpu.__file__), "models")
    for module, fields in NEEDS.items():
        path = os.path.join(models, module + ".py")
        text = open(path).read() if os.path.exists(path) else ""
        for field in fields:
            if not re.search(rf"^\s+{field}\s*:", text, re.M):
                raise spec.SpecError(
                    f"family sambay needs the config field {field!r} of {path}, "
                    f"which is not there: this checkout's program cannot run it")


def layer_types(hf: Dict[str, Any], n_layers: int) -> Tuple[str, ...]:
    """The kind of each of the first ``n_layers`` layers, by the published rule."""
    if hf["mb_per_layer"] != 2 or n_layers % 4:
        raise ValueError("the rule is written for mb_per_layer 2 and an even half")
    half = n_layers // 2
    return tuple(("mamba" if l <= half else "gmu") if l % 2 == 0 else
                 ("window" if l < half else "full" if l == half + 1 else "cross")
                 for l in range(n_layers))


def _sizes(cfg_file: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys and the assumed sizes the arithmetic and the
    reference read, under one name each."""
    hf, assumed = cfg_file["config"], cfg_file["assumed"]
    mamba = assumed["mamba"]
    return {**hf, "head_dim": hf["hidden_size"] // hf["num_attention_heads"],
            "softmax_scale": assumed["softmax_scale"]["value"],
            "d_state": mamba["d_state"], "d_conv": mamba["d_conv"],
            "expand": mamba["expand"], "dt_rank": mamba["dt_rank"]}


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import sambay

    hf = _sizes(cfg_file)
    if hf["dt_rank"] != math.ceil(hf["hidden_size"] / 16):
        raise ValueError("dt_rank is ceil(hidden_size / 16)")
    return sambay.SambaYConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"], n_layers=n_layers,
        n_heads=hf["num_attention_heads"], n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=float(hf["layer_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]), param_dtype=jnp.bfloat16,
        attn_impl=attn_impl, loss_chunk=loss_chunk, use_rope=False,
        attn_scale=float(hf["softmax_scale"]),
        layer_types=layer_types(hf, n_layers),
        sliding_window=hf["sliding_window"], mamba_d_state=hf["d_state"],
        mamba_d_conv=hf["d_conv"], mamba_expand=hf["expand"],
        mamba_dt_rank=hf["dt_rank"])


def init_params(rng, cfg):
    from ray_tpu.models import sambay

    return sambay.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim", "layer_norm_eps",
        "sliding_window", "softmax_scale", "d_state", "d_conv", "dt_rank")


def _static(cfg_file: Dict[str, Any]) -> Tuple:
    sizes = _sizes(cfg_file)
    return tuple((k, sizes[k]) for k in KEYS)


def _ln(x, w, b, eps):
    import jax
    import jax.numpy as jnp

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w + b


def _keys_values(u, layer, hf):
    """u [s, d] -> (k, v) [s, hkv, hd]."""
    s = u.shape[0]
    hkv, hd = hf["num_key_value_heads"], hf["head_dim"]
    return ((u @ layer["wk"].T + layer["bk"]).reshape(s, hkv, hd),
            (u @ layer["wv"].T + layer["bv"]).reshape(s, hkv, hd))


def _diff_attention(u, kv, layer, hf, depth, window: Optional[int]):
    """u [s, d] and the keys and values it attends to -> [s, d]: one pair of
    heads at a time, two softmaxes over the whole sequence each."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    hq, hkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    k, v = kv
    q = (u @ layer["wq"].T + layer["bq"]).reshape(s, hq // 2, 2, hd)
    # pair j reads the key/value pair j // (hq / hkv)
    kp = jnp.repeat(k.reshape(s, hkv // 2, 2, hd), hq // hkv, axis=1)
    vp = jnp.repeat(v.reshape(s, hkv // 2, 2 * hd), hq // hkv, axis=1)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (i >= j) if window is None else (i >= j) & (i - j < window)
    lq1, lk1, lq2, lk2 = layer["lambdas"]
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init

    def pair(qkv):
        qj, kj, vv = qkv  # [s, 2, hd], [s, 2, hd], [s, 2 hd]
        a1 = jax.nn.softmax(jnp.where(
            seen, qj[:, 0] @ kj[:, 0].T * hf["softmax_scale"], -jnp.inf), axis=-1)
        a2 = jax.nn.softmax(jnp.where(
            seen, qj[:, 1] @ kj[:, 1].T * hf["softmax_scale"], -jnp.inf), axis=-1)
        o = (a1 - lam * a2) @ vv
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + hf["layer_norm_eps"]) * layer["subln"]
        return (1.0 - lam_init) * o

    out = jax.lax.map(pair, (q.transpose(1, 0, 2, 3), kp.transpose(1, 0, 2, 3),
                             vp.transpose(1, 0, 2)))           # [hq/2, s, 2 hd]
    return out.transpose(1, 0, 2).reshape(s, hq * hd) @ layer["wo"] + layer["bo"]


def _mamba_mixer(u, layer, hf):
    """u [s, d] -> (out [s, d], the memory y [s, d_inner], the state after the
    last token [d_inner, d_state])."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    n, kc, r = hf["d_state"], hf["d_conv"], hf["dt_rank"]
    di = layer["out_proj"].shape[0]
    xz = u @ layer["in_proj"]
    xs, z = xz[:, :di], xz[:, di:]
    padded = jnp.concatenate([jnp.zeros((kc - 1, di), xs.dtype), xs])
    xs = jax.nn.silu(sum(layer["conv_w"][j] * padded[j:j + s] for j in range(kc))
                     + layer["conv_b"])
    dbc = xs @ layer["x_proj"].T
    delta, bm, cm = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
    dt = jax.nn.softplus(delta @ layer["dt_proj"] + layer["dt_bias"])   # [s, di]
    a = -jnp.exp(layer["A_log"]).T                                      # [di, n]

    def step(h, t):
        x_t, dt_t, b_t, c_t = t                        # [di] [di] [n] [n]
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    state, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (xs, dt, bm, cm))
    y = y + layer["D"] * xs
    return (y * jax.nn.silu(z)) @ layer["out_proj"], y, state


def _layer(x, carried, stack, index, depth, *, kind: str, static: Tuple):
    """One layer over x [s, d] float32, its weights upcast from the kind's stack.
    ``carried``: what later layers read of earlier ones, the last mamba layer's
    memory ``m``, the full layer's ``k`` and ``v``, and the mamba layers' final
    states so far; returns (x, carried)."""
    import jax
    import jax.numpy as jnp

    hf = dict(static)
    with jax.default_matmul_precision("highest"):
        layer = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
            w, index, 0, False).astype(jnp.float32), stack)
        eps = hf["layer_norm_eps"]
        carried = dict(carried)
        if kind == "mamba":
            u = _ln(x, layer["ssm_norm"], layer["ssm_norm_b"], eps)
            mixed, carried["m"], carried["state"] = _mamba_mixer(u, layer, hf)
        elif kind == "gmu":
            u = _ln(x, layer["gmu_norm"], layer["gmu_norm_b"], eps)
            mixed = (carried["m"] * jax.nn.silu(u @ layer["w1"])) @ layer["w2"]
        else:
            u = _ln(x, layer["attn_norm"], layer["attn_norm_b"], eps)
            if kind == "full":
                carried["k"], carried["v"] = _keys_values(u, layer, hf)
            kv = (_keys_values(u, layer, hf) if kind == "window"
                  else (carried["k"], carried["v"]))
            mixed = _diff_attention(u, kv, layer, hf, depth,
                                    hf["sliding_window"] if kind == "window" else None)
        x = x + mixed
        u = _ln(x, layer["mlp_norm"], layer["mlp_norm_b"], eps)
        x = x + (jax.nn.silu(u @ layer["w_gate"]) * (u @ layer["w_up"])) @ layer["w_down"]
        return x, carried


@functools.cache
def _jitted_layer():
    import jax

    return functools.partial(jax.jit, static_argnames=("kind", "static"))(_layer)


def hidden(params, tokens, cfg_file: Dict[str, Any], states: Optional[list] = None):
    """tokens [s] -> the final-norm hidden [s, d] float32, every layer over
    every token. ``states``, a list, takes every mamba layer's state after the
    last token, in order."""
    import jax
    import jax.numpy as jnp

    hf, static = cfg_file["config"], _static(cfg_file)
    layer, seen = _jitted_layer(), dict.fromkeys(KINDS, 0)
    x = params["embed"][tokens].astype(jnp.float32)
    depth = sum(jax.tree.leaves(params["layers"][kind])[0].shape[0] for kind in KINDS)
    carried: Dict[str, Any] = {}
    for l, kind in enumerate(layer_types(hf, depth)):
        x, carried = layer(x, carried, params["layers"][kind], jnp.int32(seen[kind]),
                           jnp.float32(l), kind=kind, static=static)
        if states is not None and kind == "mamba":
            states.append(carried["state"])
        seen[kind] += 1
    return _ln(x, params["final_norm"].astype(jnp.float32),
               params["final_norm_b"].astype(jnp.float32), hf["layer_norm_eps"])


VOCAB_BLOCKS = 8


@functools.cache
def _margins_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def margins(x, embed, following):
        """The vocabulary goes a block of its rows at a time: [s, V / 8]
        float32, never [s, V] nor the head in float32 whole (2.05 GB)."""
        with jax.default_matmul_precision("highest"):
            v, s = embed.shape[0], x.shape[0]
            nb = VOCAB_BLOCKS if v % VOCAB_BLOCKS == 0 else 1
            width = v // nb

            def one(carry, block):
                best, scale, finite, took = carry
                rows, first = block
                out = x @ rows.astype(jnp.float32).T                   # [s, width]
                at = following - first
                mine = (at >= 0) & (at < width)
                got = jnp.take_along_axis(
                    out, jnp.clip(at, 0, width - 1)[:, None], axis=-1)[:, 0]
                return (jnp.maximum(best, out.max(-1)),
                        jnp.maximum(scale, jnp.abs(out).max(-1)),
                        finite & jnp.isfinite(out).all(-1),
                        jnp.where(mine, got, took)), None

            start = (jnp.full((s,), -jnp.inf), jnp.zeros((s,)),
                     jnp.ones((s,), bool), jnp.zeros((s,)))
            (best, scale, finite, took), _ = jax.lax.scan(
                one, start, (embed.reshape(nb, width, -1), jnp.arange(nb) * width))
            return {"margin": best - took, "scale": scale, "finite": finite}

    return margins


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V]."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return jnp.stack([hidden(params, row, cfg_file)
                          @ params["embed"].astype(jnp.float32).T for row in tokens])


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's: for one sequence ``tokens`` [1, s] and the token
    that followed each position, how far that token's logit lies under the
    position's best, the logits' largest magnitude there and whether they are
    finite; every row is computed and ``rows`` changes nothing."""
    return _margins_fn()(hidden(params, tokens[0], cfg_file), params["embed"],
                         following)


def final_states(params, tokens, cfg_file: Dict[str, Any]):
    """The mamba layers' states [L_mamba, d_state, d_inner] float32 (as the
    program keeps them) after the last of ``tokens`` [s]."""
    import jax.numpy as jnp

    states: list = []
    hidden(params, tokens, cfg_file, states)
    return jnp.stack(states).swapaxes(1, 2)


def loss(params, tokens, cfg_file: Dict[str, Any]):
    """Next-token cross entropy of tokens [b, s+1]."""
    import jax
    import jax.numpy as jnp

    out = logits(params, tokens[:, :-1], cfg_file)
    logp = jax.nn.log_softmax(out, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    ce = nll.mean()
    return {"loss": ce, "ce": ce, "aux": jnp.float32(0)}


# ---- the arithmetic ------------------------------------------------------------
# ``hf`` is the published ``config``; the sizes it lacks are the modeling file's
# defaults (the configuration file's ``assumed``), written here as the rule that
# gives them

def _head_dim(hf: Dict[str, Any]) -> int:
    return hf["hidden_size"] // hf["num_attention_heads"]


D_STATE, D_CONV, EXPAND = 16, 4, 2


def _d_inner(hf: Dict[str, Any]) -> int:
    return EXPAND * hf["hidden_size"]


def _dt_rank(hf: Dict[str, Any]) -> int:
    return math.ceil(hf["hidden_size"] / 16)


def _count(hf: Dict[str, Any], n_layers: int) -> Dict[str, int]:
    kinds = layer_types(hf, n_layers)
    return {kind: kinds.count(kind) for kind in KINDS}


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms, biases, the
    convolution, the lambdas and the per-channel vectors left out): a mamba
    layer's four projections, an attention layer's four (a cross layer's two),
    a gated memory unit's two, and every layer's SwiGLU."""
    d, n, di = hf["hidden_size"], _count(hf, n_layers), _d_inner(hf)
    q = hf["num_attention_heads"] * _head_dim(hf)
    kv = hf["num_key_value_heads"] * _head_dim(hf)
    mamba = d * 2 * di + di * (_dt_rank(hf) + 2 * D_STATE) + _dt_rank(hf) * di + di * d
    attention = d * q + 2 * d * kv + q * d
    return (n["mamba"] * mamba + (n["window"] + n["full"]) * attention
            + n["cross"] * 2 * d * q + n["gmu"] * 2 * d * di
            + n_layers * 3 * d * hf["intermediate_size"])


def total_params(hf: Dict[str, Any], n_layers: int) -> int:
    """Every parameter: the matrices, the tied embedding once, the LayerNorms
    (weight and bias: two a layer and the final one), the attention
    projections' biases, four lambda vectors and a sub-norm a layer that
    attends, and a mamba layer's convolution with its bias, ``dt``'s bias,
    ``A_log`` and ``D``."""
    d, n, di, hd = hf["hidden_size"], _count(hf, n_layers), _d_inner(hf), _head_dim(hf)
    q, kv = hf["num_attention_heads"] * hd, hf["num_key_value_heads"] * hd
    heads = 4 * hd + 2 * hd
    mamba = (D_CONV + 1) * di + di + D_STATE * di + di
    head = 0 if hf["tie_word_embeddings"] else d * hf["vocab_size"]
    return (hf["vocab_size"] * d + head + 2 * d + matmul_params(hf, n_layers, False)
            + n_layers * 4 * d + n["mamba"] * mamba
            + (n["window"] + n["full"]) * (q + 2 * kv + d + heads)
            + n["cross"] * (q + d + heads))


def weight_bytes(hf: Dict[str, Any], n_layers: int, itemsize: int = 2) -> int:
    """What one decode step has to read of the weights: all of them, the tied
    embedding once as the head."""
    return itemsize * total_params(hf, n_layers)


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """As the dense family's: a window layer sees ``sliding_window`` keys at
    most, the full layer and the cross layers the whole sequence."""
    n = _count(hf, n_layers)
    seen = (n["window"] * min(seq, hf["sliding_window"])
            + (n["full"] + n["cross"]) * seq)
    return seen * hf["num_attention_heads"] * _head_dim(hf)


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a row keeps of one position for as long as it lives: the one full
    layer's keys and values, which ``kv_readers`` layers read a step."""
    return (2 * _count(hf, n_layers)["full"] * hf["num_key_value_heads"]
            * _head_dim(hf) * itemsize)


def kv_readers(hf: Dict[str, Any], n_layers: int) -> int:
    """Layers that read the shared keys and values in a decode step."""
    n = _count(hf, n_layers)
    return n["full"] + n["cross"]


def window_bytes_per_row(hf: Dict[str, Any], n_layers: int, itemsize: int = 2) -> int:
    """A row's rings: every window layer's last ``sliding_window`` keys and
    values, whatever the row's length. A decode step reads the live ones."""
    return (2 * _count(hf, n_layers)["window"] * hf["sliding_window"]
            * hf["num_key_value_heads"] * _head_dim(hf) * itemsize)


def state_bytes_per_row(hf: Dict[str, Any], n_layers: int, state_itemsize: int = 4,
                        tail_itemsize: int = 2) -> int:
    """What a row holds whatever its position: every mamba layer's state
    [d_inner, d_state] and the ``d_conv - 1`` inputs the convolution still
    needs. A decode step reads it once and writes it once."""
    di = _d_inner(hf)
    return _count(hf, n_layers)["mamba"] * (
        di * D_STATE * state_itemsize + (D_CONV - 1) * di * tail_itemsize)
