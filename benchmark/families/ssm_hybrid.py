"""The ssm_hybrid family (state-space layers beside attention): IBM's Granite 4.0 "H" decoder as transformers
publishes it (``granitemoehybrid``: ``GraniteMoeHybridDecoderLayer`` with
``shared_mlp`` only, ``GraniteMoeHybridMambaLayer`` / ``mamba_ssm``'s
Mamba-2) and ``ray_tpu/models/hybrid.py`` serves it. Per token, ``x`` the
residual stream, ``eps`` = ``rms_norm_eps``:

- ``x = embedding_multiplier * E[token]``;
- every layer: ``x += residual_multiplier * Mixer(RMSNorm(x))``, then
  ``x += residual_multiplier * SwiGLU(RMSNorm(x))``, width
  ``shared_intermediate_size``;
- an ``attention`` layer's mixer: grouped-query causal attention with NO
  rotation and the softmax scale ``attention_multiplier`` (not 1/sqrt(head));
- a ``mamba`` layer's mixer, ``u`` the normed input: ``[z | xBC | dt] = u W_in``;
  a causal depthwise convolution over the last ``mamba_d_conv`` positions of
  ``xBC`` with bias, then ``silu``, split into ``x`` (heads x channels), ``B``
  and ``C``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state
  ``h`` [head, channel, state], zero at the start, ``h_t = exp(dt A) h_{t-1} +
  dt x_t (x) B_t``, ``y_t = h_t C_t + D x_t``; ``RMSNorm(y * silu(z)) * w`` over
  all channels; ``W_out``;
- ``logits = RMSNorm(x) E^T / logits_scaling`` (tied).

The reference below follows that to the letter in float32 ``jax.numpy`` at
matmul precision ``highest``: the recurrence is a ``lax.scan`` over time with
the state as its carry, layers are taken one at a time in ``layer_types``'
order, there is no cache and no chunking, and of ``ray_tpu.models`` only the
parameter tree is taken (stacked by layer kind). Departures, set out in the
configuration file's ``assumed``: the published config has no key for the
state's type (float32 here and in the program), the head size (hidden /
heads) or the weights' initialisation; ``num_local_experts`` is 0, so there
is no routed feed-forward and ``intermediate_size`` is not used. One group
of ``B`` and ``C`` per ``mamba_n_groups``; head ``i`` reads group ``i // (heads
/ groups)``. Importing this file imports neither JAX nor the program; its
functions do."""

import functools
from typing import Any, Dict, Optional, Tuple

from benchmark.lib import spec

# ---- the program's config and weights ----------------------------------------

# what the checkout's program must have for this family: the module and,
# in it, the config's fields
NEEDS = {"hybrid": ("layer_types", "mamba_d_state"),
         "llama": ("attn_scale", "residual_multiplier")}


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program cannot build
    this family's config (``ray_tpu/models`` before PR 31 has no
    ``hybrid.py``). The cell's readers call it as the parent process loads
    them, before a replica is deployed: ``serve.run`` starts a replica whose
    constructor raises again and again, so a run on such a checkout would
    hang where it has to fail. Reads the source and imports nothing (the
    parent process stays off JAX)."""
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
                    f"family ssm_hybrid needs the config field {field!r} "
                    f"of {path}, which is not there: this checkout's program "
                    f"cannot run it")


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import hybrid

    hf = cfg_file["config"]
    if hf["num_local_experts"] or hf["position_embedding_type"] != "nope":
        raise ValueError("the family has no routed experts and no rotation")
    return hybrid.HybridConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["shared_intermediate_size"], max_seq_len=max_seq_len,
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk,
        use_rope=False, attn_scale=float(hf["attention_multiplier"]),
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        layer_types=tuple(hf["layer_types"][:n_layers]),
        mamba_n_heads=hf["mamba_n_heads"], mamba_d_head=hf["mamba_d_head"],
        mamba_d_state=hf["mamba_d_state"], mamba_n_groups=hf["mamba_n_groups"],
        mamba_d_conv=hf["mamba_d_conv"],
        mamba_chunk_size=hf["mamba_chunk_size"])


def init_params(rng, cfg):
    from ray_tpu.models import hybrid

    return hybrid.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "attention_multiplier", "embedding_multiplier", "residual_multiplier",
        "logits_scaling", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "mamba_d_conv")


def _static(cfg_file: Dict[str, Any]) -> Tuple:
    return tuple((k, cfg_file["config"][k]) for k in KEYS)


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention_mixer(u, layer, hf):
    """u [s, d] -> [s, d]: one key/value head and its query heads at a time."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = layer["wq"].shape[-1] // hq
    q = (u @ layer["wq"]).reshape(s, hkv, hq // hkv, hd)
    k = (u @ layer["wk"]).reshape(s, hkv, hd)
    v = (u @ layer["wv"]).reshape(s, hkv, hd)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(qkv):
        qg, kg, vg = qkv  # [s, hq/hkv, hd], [s, hd], [s, hd]
        scores = jnp.einsum("qgd,kd->gqk", qg, kg) * hf["attention_multiplier"]
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vg)

    out = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                              v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(s, hq * hd) @ layer["wo"]


def _mamba_mixer(u, layer, hf, want_state: bool = False):
    """u [s, d] -> [s, d] (and, asked, the state after the last token)."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    h, p, n = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    g, kc = hf["mamba_n_groups"], hf["mamba_d_conv"]
    di = h * p
    zxbcdt = u @ layer["in_proj"].T                      # stored [out, in]
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:di + di + 2 * g * n], zxbcdt[:, -h:]
    padded = jnp.concatenate([jnp.zeros((kc - 1, xbc.shape[1]), xbc.dtype), xbc])
    conv = sum(layer["conv_w"][j] * padded[j:j + s] for j in range(kc))
    xbc = jax.nn.silu(conv + layer["conv_b"])
    x = xbc[:, :di].reshape(s, h, p)
    bm = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), h // g, axis=1)
    cm = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), h // g, axis=1)
    dt = jax.nn.softplus(dt + layer["dt_bias"])          # [s, h]
    a = -jnp.exp(layer["A_log"])                         # [h]

    def step(state, t):
        x_t, b_t, c_t, dt_t = t                          # [h,p] [h,n] [h,n] [h]
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    state, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                            (x, bm, cm, dt))
    y = y + layer["D"][:, None] * x
    y = _rms(y.reshape(s, di) * jax.nn.silu(z), layer["gate_norm"],
             hf["rms_norm_eps"])
    out = y @ layer["out_proj"]
    return (out, state) if want_state else out


def _layer(x, stack, index, *, kind: str, static: Tuple, want_state: bool = False):
    """One layer over x [s, d] float32, its weights upcast from the kind's
    stack; with ``want_state`` also a mamba layer's final state."""
    import jax
    import jax.numpy as jnp

    hf = dict(static)
    with jax.default_matmul_precision("highest"):
        layer = jax.tree.map(lambda w: jax.lax.dynamic_index_in_dim(
            w, index, 0, False).astype(jnp.float32), stack)
        eps, res = hf["rms_norm_eps"], hf["residual_multiplier"]
        state = None
        if kind == "mamba":
            mixed = _mamba_mixer(_rms(x, layer["ssm_norm"], eps), layer, hf,
                                 want_state)
            if want_state:
                mixed, state = mixed
        else:
            mixed = _attention_mixer(_rms(x, layer["attn_norm"], eps), layer, hf)
        x = x + res * mixed
        u = _rms(x, layer["mlp_norm"], eps)
        y = (jax.nn.silu(u @ layer["w_gate"]) * (u @ layer["w_up"])) @ layer["w_down"]
        x = x + res * y
        return (x, state) if want_state else x


@functools.cache
def _jitted_layer():
    import jax

    return functools.partial(jax.jit, static_argnames=(
        "kind", "static", "want_state"))(_layer)


def hidden(params, tokens, cfg_file: Dict[str, Any], states: Optional[list] = None):
    """tokens [s] -> the final-norm hidden [s, d] float32. ``states``, a
    list, takes every mamba layer's state after the last token, in order."""
    import jax
    import jax.numpy as jnp

    hf, static = cfg_file["config"], _static(cfg_file)
    layer, seen = _jitted_layer(), {"mamba": 0, "attention": 0}
    x = hf["embedding_multiplier"] * params["embed"][tokens].astype(jnp.float32)
    depth = sum(jax.tree.leaves(params["layers"][kind])[0].shape[0]
                for kind in seen)
    for kind in hf["layer_types"][:depth]:
        out = layer(x, params["layers"][kind], jnp.int32(seen[kind]), kind=kind,
                    static=static, want_state=states is not None and kind == "mamba")
        if states is not None and kind == "mamba":
            x, state = out
            states.append(state)
        else:
            x = out
        seen[kind] += 1
    return _rms(x, params["final_norm"].astype(jnp.float32), hf["rms_norm_eps"])


@functools.cache
def _margins_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def margins(x, embed, following, scaling):
        with jax.default_matmul_precision("highest"):
            head = embed.astype(jnp.float32).T

            def some(args):  # a block of rows: [block, V] float32, not [s, V]
                xs, fs = args
                out = xs @ head / scaling
                took = jnp.take_along_axis(out, fs[:, None], axis=-1)[:, 0]
                return {"margin": out.max(-1) - took,
                        "scale": jnp.abs(out).max(-1),
                        "finite": jnp.isfinite(out).all(-1)}

            s = x.shape[0]
            block = 256 if s % 256 == 0 else s
            got = jax.lax.map(some, (x.reshape(s // block, block, -1),
                                     following.reshape(s // block, block)))
            return {k: v.reshape(s) for k, v in got.items()}

    return margins


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V]."""
    import jax
    import jax.numpy as jnp

    scaling = cfg_file["config"]["logits_scaling"]
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            hidden(params, row, cfg_file)
            @ params["embed"].astype(jnp.float32).T / scaling for row in tokens])


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's: for one sequence ``tokens`` [1, s] and the
    token that followed each position, how far that token's logit lies under
    the position's best, the logits' largest magnitude there and whether
    they are finite; every row is computed and ``rows`` changes nothing. The
    vocabulary projection goes 256 rows at a time."""
    x = hidden(params, tokens[0], cfg_file)
    return _margins_fn()(x, params["embed"], following,
                         cfg_file["config"]["logits_scaling"])


def final_states(params, tokens, cfg_file: Dict[str, Any]):
    """The mamba layers' states [L_mamba, h, p, n] float32 after the last of
    ``tokens`` [s]: what a served row's state is compared with."""
    import jax.numpy as jnp

    states: list = []
    hidden(params, tokens, cfg_file, states)
    return jnp.stack(states)


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

def _head_dim(hf: Dict[str, Any]) -> int:
    return hf["hidden_size"] // hf["num_attention_heads"]


def _count(hf: Dict[str, Any], n_layers: int) -> Dict[str, int]:
    kinds = hf["layer_types"][:n_layers]
    return {kind: kinds.count(kind) for kind in ("mamba", "attention")}


def _conv_dim(hf: Dict[str, Any]) -> int:
    return (hf["mamba_n_heads"] * hf["mamba_d_head"]
            + 2 * hf["mamba_n_groups"] * hf["mamba_d_state"])


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications over the first
    ``n_layers`` of ``layer_types`` (norms, the convolution and the
    per-head scalars left out): a mamba layer's two projections, an
    attention layer's four, and every layer's SwiGLU."""
    d, n = hf["hidden_size"], _count(hf, n_layers)
    di = hf["mamba_n_heads"] * hf["mamba_d_head"]
    mlp = 3 * d * hf["shared_intermediate_size"]
    mamba = d * (di + _conv_dim(hf) + hf["mamba_n_heads"]) + di * d
    q = hf["num_attention_heads"] * _head_dim(hf)
    kv = hf["num_key_value_heads"] * _head_dim(hf)
    attention = d * q + 2 * d * kv + q * d
    return n["mamba"] * (mamba + mlp) + n["attention"] * (attention + mlp)


def total_params(hf: Dict[str, Any], n_layers: int) -> int:
    """Every parameter: the matrices, the tied embedding once, the norms (two
    a layer, a mamba layer's gated one, the final one), the convolution with
    its bias and the three per-head vectors."""
    d, n = hf["hidden_size"], _count(hf, n_layers)
    di = hf["mamba_n_heads"] * hf["mamba_d_head"]
    small = ((hf["mamba_d_conv"] + 1) * _conv_dim(hf) + 3 * hf["mamba_n_heads"]
             + di)
    head = 0 if hf["tie_word_embeddings"] else d * hf["vocab_size"]
    return (hf["vocab_size"] * d + head + d + matmul_params(hf, n_layers, False)
            + n_layers * 2 * d + n["mamba"] * small)


def weight_bytes(hf: Dict[str, Any], n_layers: int, itemsize: int = 2) -> int:
    """What one decode step has to read of the weights: all of them, the
    tied embedding once as the head."""
    return itemsize * total_params(hf, n_layers)


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """As the dense family's, over the attention layers alone."""
    return (_count(hf, n_layers)["attention"] * hf["num_attention_heads"]
            * _head_dim(hf) * seq)


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step reads of one cached position: the attention
    layers' keys and values."""
    return (2 * _count(hf, n_layers)["attention"] * hf["num_key_value_heads"]
            * _head_dim(hf) * itemsize)


def state_bytes_per_row(hf: Dict[str, Any], n_layers: int, state_itemsize: int = 4,
                        tail_itemsize: int = 2) -> int:
    """What a row holds whatever its position: every mamba layer's state
    [heads, channels, states] and the ``mamba_d_conv - 1`` inputs the
    convolution still needs. A decode step reads it once and writes it once."""
    state = hf["mamba_n_heads"] * hf["mamba_d_head"] * hf["mamba_d_state"]
    tail = (hf["mamba_d_conv"] - 1) * _conv_dim(hf)
    return _count(hf, n_layers)["mamba"] * (state * state_itemsize
                                            + tail * tail_itemsize)
