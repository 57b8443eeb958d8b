"""The kimi_linear family: Kimi Linear's block as moonshotai publish it
(``config.json``, ``model_type`` ``kimi_linear``; the report, arXiv:2510.26692;
the published modeling file's ``KimiDeltaAttention``) and
``ray_tpu/models/moe.py`` trains it in its patterned form. (Named after the
maker too because ``tests/benchmark/test_benchmark_spec.py`` holds the sorted
directory to begin ``dense.py``, ``moe.py``: a family's name sorts after those.)

A layer, x the residual stream (float32 here, everything at ``highest``),
pre-norm: ``x + mixer(rms(x, attn_norm))`` then ``x + ffn(rms(x, mlp_norm))``.

- a KDA mixer (H heads of width 128, keys and values alike), h the normed
  input: ``q, k, v = silu(conv4(h @ wq)), silu(conv4(h @ wk)), silu(conv4(h @
  wv))``, a depthwise causal convolution of ``short_conv_kernel_size`` taps
  each, no bias; a head's q and k divided by their L2 norm (``rsqrt(sum of
  squares + 1e-6)``), q times ``128 ** -0.5``; log-decay a channel ``g =
  -exp(A_log)[head] * softplus((h @ f_down) @ f_up + dt_bias)``; ``beta =
  sigmoid(h @ wb)``; a head's state S [128, 128] from zeros, a token at a
  time: ``S' = exp(g_t)[:, None] * S; S = S' + beta_t k_t (v_t - S'^T k_t)^T;
  o_t = S^T q_t`` (``_delta_rule``: a ``lax.scan`` over the tokens, which
  owes nothing to the chunked algebra of ``ray_tpu/ops/kda.py``); ``o =
  rms(o, o_norm) * sigmoid((h @ g_down) @ g_up + g_bias)`` over each head's
  128; ``wo``;
- an MLA mixer (H heads): ``q = h @ wq`` [H, 128 + 64]; ``[c, k_pe] = h @
  wkv_a`` [512], [64]; ``c = rms(c, kv_norm)``; ``[k_nope, v] = c @ wkv_b``
  [H, 128 + 128]; a head's key ``[k_nope ; k_pe]``, ``k_pe`` one for all
  heads; nothing is rotated (``mla_use_nope``); scores over ``sqrt(192)``,
  causal softmax a block of queries at a time, values 128 wide; ``wo``;
- the feed-forward half: layer 1 a SwiGLU of ``intermediate_size``; an expert
  layer scores ``s = sigmoid(h @ router)`` over all published experts in
  float32, chooses the top K of ``s + router_bias``, weighs them ``s[sel] /
  sum(s[sel]) * routed_scaling_factor`` and adds the shared expert: the afmoe
  family's ``_experts`` under this config's key names (one rule, one copy);
- a final norm, the head untied.

The chip's share is as the afmoe family's: ``config`` holds the keys as run
(``num_experts`` held here, ``vocab_size`` the slice) with
``num_experts_published`` and ``layers_run`` beside them; ``layers_run``
counts from 1, as ``linear_attn_config``'s two lists do.

Departures are set out in the configuration file's ``assumed``. Importing
this file imports neither JAX nor the program; its functions do, and the
reference imports nothing of ``ray_tpu``.
"""

import functools
from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import spec

# ---- the program's config and weights ----------------------------------------

# the program's mixer of each published list of ``linear_attn_config``
KINDS = {"kda_layers": "kda", "full_attn_layers": "mla"}
# tokens a chunk of the chunked delta rule, the published kernels' and the
# program's (``ray_tpu/ops/kda.CHUNK``): what the arithmetic counts
KDA_CHUNK = 64


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program has no ``kda``
    kind of layer (``ray_tpu/models/moe.py`` before PR 48), as the afmoe
    family's does: called by the cell's new readers as the parent process
    loads them, so that a checkout that cannot train the cell fails in
    seconds, before it starts a trainer. Reads the source and imports
    nothing of JAX."""
    import os
    import re

    import ray_tpu

    path = os.path.join(os.path.dirname(ray_tpu.__file__), "models", "moe.py")
    with open(path) as f:
        if not re.search(r'^ATTN_KINDS\s*=.*"kda"', f.read(), re.M):
            raise spec.SpecError(
                f"family moonshot_kimi_linear needs the layer kind 'kda', "
                f"which {path} does not have: this checkout's program "
                f"cannot run it")


def layers_run(hf: Dict[str, Any], n_layers: int) -> List[int]:
    """The published numbers, from 1, of the first ``n_layers`` layers run."""
    run = list(hf.get("layers_run") or range(1, hf["num_hidden_layers"] + 1))
    if n_layers > len(run):
        raise spec.SpecError(f"{n_layers} layers asked of {len(run)}")
    return run[:n_layers]


def kinds(hf: Dict[str, Any], n_layers: int) -> Tuple[str, ...]:
    """The program's kind of each layer run, from ``linear_attn_config``."""
    of = {i: kind for name, kind in KINDS.items()
          for i in hf["linear_attn_config"][name]}
    return tuple(of[i] for i in layers_run(hf, n_layers))


def dense_layers_run(hf: Dict[str, Any], n_layers: int) -> int:
    return sum(i <= hf["first_k_dense_replace"]
               for i in layers_run(hf, n_layers))


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import moe

    hf, assumed = cfg_file["config"], cfg_file["assumed"]
    kda = hf["linear_attn_config"]
    return moe.MoEConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], attn_head_dim=hf["head_dim"],
        d_ff=hf["moe_intermediate_size"], d_ff_dense=hf["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk,
        layer_kinds=kinds(hf, n_layers),
        n_dense_layers=dense_layers_run(hf, n_layers),
        n_experts=hf.get("num_experts_published", hf["num_experts"]),
        n_experts_held=hf["num_experts"], top_k=hf["num_experts_per_token"],
        n_shared_experts=hf["num_shared_experts"],
        router_score=hf["moe_router_activation_func"], router_bias=True,
        norm_topk_prob=bool(hf["moe_renormalize"]),
        route_scale=float(hf["routed_scaling_factor"]), balance="sequence",
        router_aux_coef=float(assumed["balance_coefficient"]),
        capacity_factor=float(assumed["capacity_factor"]),
        kda_heads=kda["num_heads"], kda_head_dim=kda["head_dim"],
        kda_conv_taps=kda["short_conv_kernel_size"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"])


def init_params(rng, cfg):
    from ray_tpu.models import moe

    return moe.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

QUERY_BLOCK = 256  # rows of scores at once: 32 heads x 16384^2 float32 is 34 GB
L2_EPS = 1e-6


def _afmoe():
    return spec.load_family("trinity_afmoe", spec.root_of(__file__))


def _static(cfg_file: Dict[str, Any], capacity_factor: Optional[float]) -> Tuple:
    """What a compiled layer reads of the configuration, hashable: this
    family's own keys, and the expert half's under the names the afmoe
    family's ``_experts`` reads them by."""
    hf, kda = cfg_file["config"], cfg_file["config"]["linear_attn_config"]
    return (
        ("rms_norm_eps", hf["rms_norm_eps"]),
        ("heads", hf["num_attention_heads"]),
        ("kda_heads", kda["num_heads"]), ("kda_head_dim", kda["head_dim"]),
        ("kv_lora_rank", hf["kv_lora_rank"]),
        ("qk_nope_head_dim", hf["qk_nope_head_dim"]),
        ("v_head_dim", hf["v_head_dim"]),
        ("num_experts_published", hf.get("num_experts_published",
                                         hf["num_experts"])),
        ("num_experts", hf["num_experts"]),
        ("num_experts_per_tok", hf["num_experts_per_token"]),
        ("score_func", hf["moe_router_activation_func"]),
        ("route_norm", hf["moe_renormalize"]),
        ("route_scale", hf["routed_scaling_factor"]),
        ("capacity_factor", capacity_factor))


def _delta_rule(q, k, v, g, beta):
    """q, k, g [b, s, H, dk], v [b, s, H, dv], beta [b, s, H] -> o [b, s, H,
    dv]: the recurrence as it is written, a token at a time from S = 0."""
    import jax
    import jax.numpy as jnp

    def token(S, at):
        q_t, k_t, v_t, g_t, b_t = at                         # [b, H, .]
        S = jnp.exp(g_t)[..., None] * S                      # [b, H, dk, dv]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))
        S = S + k_t[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    b, _, H, dk = q.shape
    S0 = jnp.zeros((b, H, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(token, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _kda(x, layer, hf: Dict[str, Any]):
    """The KDA mixer, residual included."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = x.shape
    H, w = hf["kda_heads"], hf["kda_head_dim"]
    h = ref.rms(x, layer["attn_norm"], hf["rms_norm_eps"])

    def mixed(which):
        y, taps = h @ layer["w" + which], layer["conv_" + which]
        n = taps.shape[0]
        past = jnp.pad(y, ((0, 0), (n - 1, 0), (0, 0)))  # zeros before token 0
        y = sum(past[:, j:j + s] * taps[j] for j in range(n))
        return jax.nn.silu(y).reshape(b, s, H, w)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + L2_EPS)

    q, k, v = unit(mixed("q")) / jnp.sqrt(ref.F32(w)), unit(mixed("k")), mixed("v")
    a = (h @ layer["f_down"]) @ layer["f_up"] + layer["dt_bias"]
    g = -jnp.exp(layer["A_log"])[:, None] * jax.nn.softplus(a).reshape(b, s, H, w)
    beta = jax.nn.sigmoid(h @ layer["wb"])
    o = _delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ layer["g_down"]) @ layer["g_up"] + layer["g_bias"])
    o = ref.rms(o, layer["o_norm"], hf["rms_norm_eps"]) * gate.reshape(b, s, H, w)
    return x + o.reshape(b, s, H * w) @ layer["wo"]


def _mla(x, layer, hf: Dict[str, Any]):
    """The MLA mixer, residual included, a block of queries at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = x.shape
    H, r = hf["heads"], hf["kv_lora_rank"]
    nope, dv = hf["qk_nope_head_dim"], hf["v_head_dim"]
    h = ref.rms(x, layer["attn_norm"], hf["rms_norm_eps"])
    q = (h @ layer["wq"]).reshape(b, s, H, -1)
    down = h @ layer["wkv_a"]
    c = ref.rms(down[..., :r], layer["kv_norm"], hf["rms_norm_eps"])
    up = (c @ layer["wkv_b"]).reshape(b, s, H, nope + dv)
    k_pe = jnp.broadcast_to(down[:, :, None, r:], (b, s, H, down.shape[-1] - r))
    k, v = jnp.concatenate([up[..., :nope], k_pe], -1), up[..., nope:]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    kpos = jnp.arange(s)

    def rows(args):  # queries [b, block, H, 192] starting at ``first``
        qb, first = args
        seen = kpos[None, :] <= (first + jnp.arange(block))[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(
            ref.F32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    qs = q.reshape(b, s // block, block, H, -1).swapaxes(0, 1)
    out = jax.lax.map(rows, (qs, jnp.arange(0, s, block)))
    return x + out.swapaxes(0, 1).reshape(b, s, H * dv) @ layer["wo"]


def _block(x, layer, hf: Dict[str, Any], kind: str, dense: bool):
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, d = x.shape
    x = (_kda if kind == "kda" else _mla)(x, layer, hf)
    h = ref.rms(x, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
    if dense:
        f, aux = ref.in_chunks(functools.partial(
            ref.swiglu, gate=layer["w_gate"], up=layer["w_up"],
            down=layer["w_down"]), h), jnp.float32(0)
    else:
        f, aux = _afmoe()._experts(h, layer, hf, b)
    return x + f.reshape(b, s, d), aux


def _layer_fn():
    """The compiled layer, built on first use (importing this file imports
    no JAX). ``layers`` is a segment's tree as the program keeps it: a
    layer's own leaves at ``index``, its mixer's in the sub-tree of its kind
    at ``kind_index``."""
    import jax

    @functools.partial(jax.jit, static_argnames=("kind", "dense", "static"))
    def layer_fn(x, layers, index, kind_index, *, kind, dense, static):
        def at(i):
            return lambda a: jax.lax.dynamic_index_in_dim(
                a, i, 0, False).astype(jax.numpy.float32)

        with jax.default_matmul_precision("highest"):
            layer = {name: at(index)(a) for name, a in layers.items()
                     if not isinstance(a, dict)}
            layer.update(jax.tree.map(at(kind_index), layers[kind]))
            return _block(x, layer, dict(static), kind, dense)

    return layer_fn


_layer = None


def hidden(params, tokens, cfg_file: Dict[str, Any],
           capacity_factor: Optional[float] = None, round_to=None):
    """tokens [b, s] -> (final-norm hidden [b, s, d] float32, mean of the
    expert layers' balancing terms), over as many layers as ``params``
    holds. ``round_to`` a dtype: every weight and the residual stream after
    every layer pass through it, which is this reference computed in that
    precision (the loss limit's control)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    global _layer
    if _layer is None:
        _layer = _layer_fn()
    hf = cfg_file["config"]
    static = _static(cfg_file, capacity_factor)
    segments = [params[name] for name in ("dense_layers", "layers")
                if name in params]
    sizes = [seg["attn_norm"].shape[0] for seg in segments]
    run = kinds(hf, sum(sizes))
    if round_to is not None:
        params = jax.tree.map(lambda a: a.astype(round_to), params)
        segments = [params[name] for name in ("dense_layers", "layers")
                    if name in params]
    x = params["embed"][tokens].astype(ref.F32)
    aux, first = ref.F32(0), 0
    for seg, size in zip(segments, sizes):
        mine = run[first:first + size]
        for i, kind in enumerate(mine):
            x, a = _layer(x, seg, jnp.int32(i), jnp.int32(mine[:i].count(kind)),
                          kind=kind, dense="router" not in seg, static=static)
            if round_to is not None:
                x = x.astype(round_to).astype(ref.F32)
            aux = aux + a
        first += size
    x = ref.rms(x, params["final_norm"].astype(ref.F32), hf["rms_norm_eps"])
    return x, aux / max(1, sizes[-1] if "layers" in params else 1)


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
    capacity that ``assumed`` sets, and the balancing term under
    ``assumed``'s coefficient. ``round_to``: as ``hidden``'s."""
    from benchmark.lib import reference as ref

    assumed = cfg_file["assumed"]
    x, aux = hidden(params, tokens[:, :-1], cfg_file,
                    assumed.get("capacity_factor"), round_to=round_to)
    head = params["lm_head"]
    if round_to is not None:
        head = head.astype(round_to)
    ce = ref._sequence_nll(x, tokens[:, 1:], head)
    return {"loss": ce + assumed["balance_coefficient"] * aux, "ce": ce,
            "aux": aux}


# ---- the arithmetic ------------------------------------------------------------

def kda_matmul_params(hf: Dict[str, Any]) -> int:
    """One KDA mixer's matrices: q, k, v and o, the two low-rank pairs and
    beta's (the taps, the biases and ``A_log`` multiply no matrix)."""
    d, kda = hf["hidden_size"], hf["linear_attn_config"]
    w = kda["head_dim"]
    ch = kda["num_heads"] * w
    return 4 * d * ch + 2 * (d * w + w * ch) + d * kda["num_heads"]


def mla_matmul_params(hf: Dict[str, Any]) -> int:
    """One MLA mixer's matrices: q, the down- and up-projection and o."""
    d, H, r = hf["hidden_size"], hf["num_attention_heads"], hf["kv_lora_rank"]
    nope, rope, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                      hf["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + dv)
            + H * dv * d)


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms, taps, biases
    and the selection bias left out). An expert layer: the shared expert,
    the router at its published width and the routed experts held here, or
    (``active_only``) the visits a token pays them on average:
    ``num_experts_per_token * held / published`` experts' worth."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    published = hf.get("num_experts_published", hf["num_experts"])
    routed = (hf["num_experts_per_token"] * hf["num_experts"] / published
              if active_only else hf["num_experts"])
    dense = dense_layers_run(hf, n_layers)
    sparse = (hf["num_shared_experts"] + routed) * 3 * d * f + d * published
    mixing = sum(kda_matmul_params(hf) if kind == "kda" else mla_matmul_params(hf)
                 for kind in kinds(hf, n_layers))
    return int(mixing + dense * 3 * d * hf["intermediate_size"]
               + (n_layers - dense) * sparse)


def kda_madds_per_token(hf: Dict[str, Any]) -> int:
    """Multiply-adds of one KDA layer's chunked recurrence for one token,
    forward, from the chunk form's products a head (``ray_tpu/ops/kda.py``),
    C the chunk, w the head width: five of C x w (``K K^T``, ``Q K^T``, ``T
    K``, ``T V``, ``A U~``) and three of w x w (``W S``, ``Q S``, ``K^T
    U~``). The sums over pairs inside a sub-block, the inverse and the
    elementwise decays are left out."""
    kda = hf["linear_attn_config"]
    w = kda["head_dim"]
    return kda["num_heads"] * w * (5 * KDA_CHUNK + 3 * w)


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Multiply-adds of the mixers' own products (no projection) for one
    token of a ``seq``-token sequence, forward, each layer by its kind,
    counted like a matrix's parameters (6 operations each forward and
    backward): an MLA layer's scores at 192 and values at 128 over the
    ``seq / 2`` keys a query sees on average; a KDA layer's chunk form."""
    mla = hf["num_attention_heads"] * (
        hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]
    ) * seq / 2.0
    return sum(kda_madds_per_token(hf) if kind == "kda" else mla
               for kind in kinds(hf, n_layers))


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step would read of one cached position: the latent and
    the shared key columns of every MLA layer; a KDA layer keeps a state and
    no position (no cell serves this family)."""
    return (kinds(hf, n_layers).count("mla")
            * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize)
