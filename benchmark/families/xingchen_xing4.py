"""The xing4_0 family: Xing4.0-29B-A4B's block as XingChen-AGI publish it
(``config.json``, ``model_type`` ``xing4_0``: the DeepSeek-V3 block, which
the keys spell out one for one, under the manifold-constrained
hyper-connections of arXiv:2512.24880, ``hc_mult`` rows and
``hc_sinkhorn_iters`` iterations) and ``ray_tpu/models/moe.py`` trains it in
its patterned form. (Named after the maker too because
``tests/benchmark/test_benchmark_spec.py`` holds the sorted directory to
begin ``dense.py``, ``moe.py``: a family's name sorts after those.)

Per token, float32 here, every product at ``highest``:

- the stream ``X`` [n, d], n = ``hc_mult``: n copies of the token's
  embedding; after the last layer the rows' sum, a final norm, the head;
- every half layer (attention and feed-forward each own ``g`` [n d], ``phi``
  [n d, n^2 + 2n], ``b`` [n^2 + 2n], ``alpha`` [3]): ``u = rms(vec(X)) g``;
  ``[p | q | R] = u phi``; ``H_pre = sigmoid(alpha_0 p + b_pre)``; ``H_post
  = 2 sigmoid(alpha_1 q + b_post)``; ``M = exp(clip(alpha_2 mat(R) + b_res,
  mhc_h_res_clamp_min, mhc_h_res_clamp_max))`` and ``hc_sinkhorn_iters``
  times ``M /= colsum(M) + hc_eps``, ``M /= rowsum(M) + hc_eps``; ``h = sum_i
  H_pre[i] X[i]``; ``y = F(rms(h))``; ``X'[i] = sum_j M[i, j] X[j] +
  H_post[i] y`` (``_read``, ``_write``: rows next to ``d`` and one einsum a
  mix, which owes nothing to ``ray_tpu/ops/hyper.py``'s rows-first slabs);
- attention (every layer): ``c_q = rms(h @ wq_a, q_norm)``; ``q = c_q @
  wq_b`` [H, 128 + 64]; ``[c, k_r] = h @ wkv_a`` [512], [64]; ``[k_n, v] =
  rms(c, kv_norm) @ wkv_b`` [H, 128 + 128]; ``k_r`` and each head's last 64
  query columns rotated on interleaved pairs at YaRN's frequencies
  (``yarn_inv_freq``); a head's key ``[k_n ; k_r]``, ``k_r`` one for all
  heads; causal softmax at ``192 ** -0.5 * m ** 2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``, a block of queries at a time; ``wo``;
- feed-forward: a leading dense layer a SwiGLU of ``intermediate_size``; an
  expert layer the afmoe family's ``_experts`` under this config's keys
  (float32 sigmoid scores over all published experts, the top K of score +
  bias, gates over their sum times ``routed_scaling_factor``, the shared
  expert, capacity in queue order; one rule, one copy);
- the prediction module (``num_nextn_predict_layers`` 1): ``h' = [rms(x,
  hnorm) ; rms(E[next token], enorm)] @ proj``, x the summed stream before
  the final norm; one expert layer on n copies of ``h'``; the rows' sum, its
  own final norm, the shared head; the token after next. ``loss = ce +
  mtp_weight ce_mtp + balance_coefficient aux``.

The chip's share is as the afmoe family's: ``config`` holds the keys as run
(``n_routed_experts`` held here, ``vocab_size`` the slice) with
``n_routed_experts_published`` and ``layers_run`` (published indices from 0)
beside them. Departures are set out in the configuration file's
``assumed``. Importing this file imports neither JAX nor the program; its
functions do, and the reference imports nothing of ``ray_tpu``.
"""

import functools
import math
from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import spec

# ---- the program's config and weights ----------------------------------------

# what ``ray_tpu/models/moe.py``'s config class must have for this family
NEEDS = ("hc_mult", "n_mtp_modules", "q_lora_rank", "mla_rope")
# a half layer's hyper-connection leaves, under ``hc_<half>_<name>``
HYPER_LEAVES = ("g", "phi", "b", "alpha")


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program cannot build
    this family's config (``ray_tpu/models/moe.py`` before PR 56 has none of
    the fields), as the afmoe family's does: called by the cell's new
    readers as the parent process loads them, so that a checkout that cannot
    train the cell fails in seconds, before it starts a trainer. Reads the
    source and imports nothing of JAX."""
    import os
    import re

    import ray_tpu

    path = os.path.join(os.path.dirname(ray_tpu.__file__), "models", "moe.py")
    with open(path) as f:
        source = f.read()
    for field in NEEDS:
        if not re.search(rf"^\s+{field}\s*:", source, re.M):
            raise spec.SpecError(
                f"family xingchen_xing4 needs the config field {field!r}, "
                f"which {path} does not have: this checkout's program "
                f"cannot run it")


def layers_run(hf: Dict[str, Any], n_layers: int) -> List[int]:
    """The published indices, from 0, of the first ``n_layers`` layers run."""
    run = list(hf.get("layers_run") or range(hf["num_hidden_layers"]))
    if n_layers > len(run):
        raise spec.SpecError(f"{n_layers} layers asked of {len(run)}")
    return run[:n_layers]


def dense_layers_run(hf: Dict[str, Any], n_layers: int) -> int:
    return sum(i < hf["first_k_dense_replace"]
               for i in layers_run(hf, n_layers))


def _published_experts(hf: Dict[str, Any]) -> int:
    return hf.get("n_routed_experts_published", hf["n_routed_experts"])


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    import jax.numpy as jnp

    from ray_tpu.models import moe
    from ray_tpu.ops.rope import Yarn

    require_program()
    hf, assumed = cfg_file["config"], cfg_file["assumed"]
    yarn = hf["rope_scaling"]
    if yarn["type"] != "yarn":
        raise spec.SpecError(f"rope_scaling of type {yarn['type']!r}")
    return moe.MoEConfig(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["moe_intermediate_size"], d_ff_dense=hf["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk,
        layer_kinds=("mla",) * n_layers,
        n_dense_layers=dense_layers_run(hf, n_layers),
        n_experts=_published_experts(hf),
        n_experts_held=hf["n_routed_experts"], top_k=hf["num_experts_per_tok"],
        n_shared_experts=hf["n_shared_experts"],
        router_score=hf["scoring_func"], router_bias=True,
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        route_scale=float(hf["routed_scaling_factor"]), balance="sequence",
        router_aux_coef=float(assumed["balance_coefficient"]),
        capacity_factor=float(assumed["capacity_factor"]),
        kv_lora_rank=hf["kv_lora_rank"], q_lora_rank=hf["q_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"], v_head_dim=hf["v_head_dim"],
        mla_rope=True, mla_yarn=Yarn(
            float(yarn["factor"]), int(yarn["original_max_position_embeddings"]),
            float(yarn["beta_fast"]), float(yarn["beta_slow"]),
            float(yarn["mscale"]), float(yarn["mscale_all_dim"])),
        hc_mult=hf["hc_mult"], hc_sinkhorn_iters=hf["hc_sinkhorn_iters"],
        hc_eps=float(hf["hc_eps"]),
        hc_clamp=(float(hf["mhc_h_res_clamp_min"]),
                  float(hf["mhc_h_res_clamp_max"])),
        n_mtp_modules=hf["num_nextn_predict_layers"],
        mtp_weight=float(assumed["mtp_weight"]))


def init_params(rng, cfg):
    from ray_tpu.models import moe

    return moe.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

QUERY_BLOCK = 256  # rows of scores at once: 32 heads x 8192^2 float32 is 8.6 GB


def _afmoe():
    return spec.load_family("trinity_afmoe", spec.root_of(__file__))


def _static(cfg_file: Dict[str, Any], capacity_factor: Optional[float]) -> Tuple:
    """What a compiled layer reads of the configuration, hashable: this
    family's own keys, and the expert half's under the names the afmoe
    family's ``_experts`` reads them by."""
    hf = cfg_file["config"]
    yarn = hf["rope_scaling"]
    return (
        ("rms_norm_eps", hf["rms_norm_eps"]),
        ("heads", hf["num_attention_heads"]),
        ("kv_lora_rank", hf["kv_lora_rank"]),
        ("qk_nope_head_dim", hf["qk_nope_head_dim"]),
        ("qk_rope_head_dim", hf["qk_rope_head_dim"]),
        ("v_head_dim", hf["v_head_dim"]),
        ("rope_theta", hf["rope_theta"]),
        ("yarn", tuple(sorted((k, v) for k, v in yarn.items() if k != "type"))),
        ("hc_mult", hf["hc_mult"]), ("hc_iters", hf["hc_sinkhorn_iters"]),
        ("hc_eps", hf["hc_eps"]),
        ("hc_clamp", (hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"])),
        ("num_experts_published", _published_experts(hf)),
        ("num_experts", hf["n_routed_experts"]),
        ("num_experts_per_tok", hf["num_experts_per_tok"]),
        ("score_func", hf["scoring_func"]),
        ("route_norm", hf["norm_topk_prob"]),
        ("route_scale", hf["routed_scaling_factor"]),
        ("capacity_factor", capacity_factor))


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: Dict[str, Any]) -> List[float]:
    """Each of the ``dim / 2`` pairs' frequency under YaRN, by the formula:
    pair i's own is ``theta ** (-2 i / dim)``; it turns ``r`` times in the
    original context at ``i(r) = dim ln(L / (2 pi r)) / (2 ln theta)``; from
    ``floor(i(beta_fast))`` down to 0 the frequency stays, from
    ``ceil(i(beta_slow))`` up it is divided by ``factor``, and between the
    two it is the linear blend."""
    def turns(r):
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (r * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(turns(yarn["beta_fast"])), 0)
    high = min(math.ceil(turns(yarn["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        own = theta ** (-2.0 * i / dim)
        out.append(own / yarn["factor"] * ramp + own * (1.0 - ramp))
    return out


def _rotate(x, hf: Dict[str, Any]):
    """x [b, s, h, rope]: the pairs (x[2i], x[2i+1]) turned by pos times
    YaRN's frequency i, times the tables' factor."""
    import jax.numpy as jnp

    yarn = dict(hf["yarn"])
    inv = jnp.asarray(yarn_inv_freq(x.shape[-1], hf["rope_theta"], yarn),
                      jnp.float32)
    scale = (yarn_mscale(yarn["factor"], yarn["mscale"])
             / yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    sin = scale * jnp.sin(ang)[None, :, None, :]
    cos = scale * jnp.cos(ang)[None, :, None, :]
    a, b = x[..., ::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(x.shape)


def _read(X, half: Dict[str, Any], hf: Dict[str, Any]):
    """X [b, s, n, d], a half's leaves -> (h [b, s, d], H_post [b, s, n],
    H_res [b, s, n, n])."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, n, d = X.shape
    z = ref.rms(X.reshape(b, s, n * d), half["g"], hf["rms_norm_eps"]) \
        @ half["phi"]
    bias, alpha = half["b"], half["alpha"]
    pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * z[..., 2 * n:] + bias[2 * n:],
                         *hf["hc_clamp"])).reshape(b, s, n, n)
    for _ in range(hf["hc_iters"]):
        m = m / (m.sum(-2, keepdims=True) + hf["hc_eps"])   # a column's sum
        m = m / (m.sum(-1, keepdims=True) + hf["hc_eps"])   # a row's
    return jnp.einsum("bsn,bsnd->bsd", pre, X), post, m


def _write(X, y, post, res):
    import jax.numpy as jnp

    return (jnp.einsum("bsij,bsjd->bsid", res, X)
            + post[..., None] * y[:, :, None, :])


def _half(layer: Dict[str, Any], which: str) -> Dict[str, Any]:
    return {name: layer[f"hc_{which}_{name}"] for name in HYPER_LEAVES}


def _mla(h, layer, hf: Dict[str, Any]):
    """The attention branch of ``h`` [b, s, d], a block of queries at a
    time (a block's scores are rebuilt in a backward, not kept)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = h.shape
    H, r = hf["heads"], hf["kv_lora_rank"]
    nope, rope, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                      hf["v_head_dim"])
    eps = hf["rms_norm_eps"]
    h = ref.rms(h, layer["attn_norm"], eps)
    q = (ref.rms(h @ layer["wq_a"], layer["q_norm"], eps)
         @ layer["wq_b"]).reshape(b, s, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], hf)], -1)
    down = h @ layer["wkv_a"]
    up = (ref.rms(down[..., :r], layer["kv_norm"], eps)
          @ layer["wkv_b"]).reshape(b, s, H, nope + dv)
    k_r = _rotate(down[:, :, None, r:], hf)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_r, (b, s, H, rope))], -1)
    v = up[..., nope:]
    yarn = dict(hf["yarn"])
    scale = (nope + rope) ** -0.5 * yarn_mscale(
        yarn["factor"], yarn["mscale_all_dim"]) ** 2
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    kpos = jnp.arange(s)

    @jax.checkpoint
    def rows(args):  # queries [b, block, H, 192] starting at ``first``
        qb, first = args
        seen = kpos[None, :] <= (first + jnp.arange(block))[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    qs = q.reshape(b, s // block, block, H, -1).swapaxes(0, 1)
    out = jax.lax.map(rows, (qs, jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, H * dv) @ layer["wo"]


def _block(X, layer, hf: Dict[str, Any], dense: bool):
    """One layer over the stream X [b, s, n, d] -> (X', the balancing
    term)."""
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _, d = X.shape
    h, post, res = _read(X, _half(layer, "attn"), hf)
    X = _write(X, _mla(h, layer, hf), post, res)
    h, post, res = _read(X, _half(layer, "mlp"), hf)
    h = ref.rms(h, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
    if dense:
        f, aux = ref.in_chunks(functools.partial(
            ref.swiglu, gate=layer["w_gate"], up=layer["w_up"],
            down=layer["w_down"]), h), jnp.float32(0)
    else:
        f, aux = _afmoe()._experts(h, layer, hf, b)
    return _write(X, f.reshape(b, s, d), post, res), aux


def _layer_fn():
    """The compiled layer, built on first use (importing this file imports
    no JAX). ``layers`` is a segment's tree as the program keeps it: a
    layer's own leaves and its mixer's under ``mla``, at ``index``. A
    backward through it keeps its input alone."""
    import jax

    @functools.partial(jax.jit, static_argnames=("dense", "static"))
    def layer_fn(X, layers, index, *, dense, static):
        def at(a):
            return jax.lax.dynamic_index_in_dim(
                a, index, 0, False).astype(jax.numpy.float32)

        @jax.checkpoint
        def run(X, layers):
            with jax.default_matmul_precision("highest"):
                layer = {name: at(a) for name, a in layers.items()
                         if not isinstance(a, dict)}
                layer.update(jax.tree.map(at, layers["mla"]))
                return _block(X, layer, dict(static), dense)

        return run(X, layers)

    return layer_fn


_layer = None


def _through(segment, X, static, dense: bool, round_to):
    """X through every layer of ``segment``; (X, the summed aux)."""
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    global _layer
    if _layer is None:
        _layer = _layer_fn()
    aux = ref.F32(0)
    for i in range(segment["attn_norm"].shape[0]):
        X, a = _layer(X, segment, jnp.int32(i), dense=dense, static=static)
        if round_to is not None:
            X = X.astype(round_to).astype(ref.F32)
        aux = aux + a
    return X, aux


def _widen(x, hf):
    import jax.numpy as jnp

    return jnp.broadcast_to(x[:, :, None, :],
                            (*x.shape[:2], hf["hc_mult"], x.shape[-1]))


def stream_through(params, X, cfg_file: Dict[str, Any],
                   capacity_factor: Optional[float] = None, round_to=None):
    """The stream X [b, s, n, d] float32 through ``params``'s leading dense
    layers and expert layers, as many as it holds -> (the stream after the
    last, the expert layers' summed balancing terms)."""
    from benchmark.lib import reference as ref

    static = _static(cfg_file, capacity_factor)
    aux = ref.F32(0)
    for name in ("dense_layers", "layers"):
        if name in params:
            X, a = _through(params[name], X, static, name == "dense_layers",
                            round_to)
            aux = aux + a
    return X, aux


def trunk(params, tokens, cfg_file: Dict[str, Any],
          capacity_factor: Optional[float] = None, round_to=None):
    """tokens [b, s] -> (the summed stream after the last layer, before the
    final norm [b, s, d] float32; the expert layers' summed balancing terms)
    over as many layers as ``params`` holds. ``round_to`` a dtype: every
    weight and the stream after every layer pass through it, which is this
    reference computed in that precision (the loss limit's control)."""
    import jax

    from benchmark.lib import reference as ref

    if round_to is not None:
        params = jax.tree.map(lambda a: a.astype(round_to), params)
    X = _widen(params["embed"][tokens].astype(ref.F32), cfg_file["config"])
    X, aux = stream_through(params, X, cfg_file, capacity_factor, round_to)
    return X.sum(2), aux


def hidden(params, tokens, cfg_file: Dict[str, Any],
           capacity_factor: Optional[float] = None, round_to=None):
    """tokens [b, s] -> (final-norm hidden [b, s, d] float32, mean of the
    trunk's expert layers' balancing terms)."""
    from benchmark.lib import reference as ref

    x, aux = trunk(params, tokens, cfg_file, capacity_factor, round_to)
    x = ref.rms(x, params["final_norm"].astype(ref.F32),
                cfg_file["config"]["rms_norm_eps"])
    return x, aux / max(1, params["layers"]["attn_norm"].shape[0])


def module_hidden(params, x, following, cfg_file: Dict[str, Any],
                  capacity_factor: Optional[float] = None, round_to=None):
    """The prediction module on ``x`` [b, s, d], the trunk's summed stream
    before the final norm, and ``following`` [b, s], the next tokens ->
    (its final-norm hidden, its layer's balancing term)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    hf, m = cfg_file["config"], params["mtp"]
    if round_to is not None:
        m = jax.tree.map(lambda a: a.astype(round_to), m)
        params = {**params, "embed": params["embed"].astype(round_to)}
    eps = hf["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        joined = jnp.concatenate([
            ref.rms(x, m["hnorm"].astype(ref.F32), eps),
            ref.rms(params["embed"][following].astype(ref.F32),
                    m["enorm"].astype(ref.F32), eps)], -1)
        h = joined @ m["proj"].astype(ref.F32)
    X, aux = _through(m["layers"], _widen(h, hf),
                      _static(cfg_file, capacity_factor), False, round_to)
    return ref.rms(X.sum(2), m["final_norm"].astype(ref.F32), eps), aux


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V] of the main head over the slice, routing
    without drops."""
    from benchmark.lib import reference as ref

    x, _ = hidden(params, tokens, cfg_file)
    return ref._project(x, params["lm_head"])


def module_logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V] of the prediction module for tokens
    [b, s + 1] (position t reads the trunk at t and token t + 1), routing
    without drops."""
    from benchmark.lib import reference as ref

    x, _ = trunk(params, tokens[:, :-1], cfg_file)
    xm, _ = module_hidden(params, x, tokens[:, 1:], cfg_file)
    return ref._project(xm, params["lm_head"])


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's, routing without drops."""
    from benchmark.lib import reference as ref

    x, _ = hidden(params, tokens, cfg_file)
    return ref._margins(x[0], params["lm_head"], following)


def loss(params, tokens, cfg_file: Dict[str, Any], round_to=None):
    """Of tokens [b, s+1] over the slice, under the capacity that ``assumed``
    sets: the next token's cross entropy ``ce``; where ``params`` holds a
    prediction module, ``ce_mtp``, the cross entropy of the token after next
    over the s - 1 positions that have one; the balancing term meaned over
    the trunk's expert layers and the module's; ``loss = ce + mtp_weight
    ce_mtp + balance_coefficient aux``. ``round_to``: as ``trunk``'s."""
    from benchmark.lib import reference as ref

    assumed, hf = cfg_file["assumed"], cfg_file["config"]
    cap = assumed.get("capacity_factor")
    x, aux = trunk(params, tokens[:, :-1], cfg_file, cap, round_to=round_to)
    head = params["lm_head"]
    if round_to is not None:
        head = head.astype(round_to)
    final = ref.rms(x, params["final_norm"].astype(ref.F32), hf["rms_norm_eps"])
    ce = ref._sequence_nll(final, tokens[:, 1:], head)
    routed = params["layers"]["attn_norm"].shape[0]
    out = {"ce": ce}
    total = ce
    if "mtp" in params:
        xm, aux_m = module_hidden(params, x, tokens[:, 1:], cfg_file, cap,
                                  round_to=round_to)
        out["ce_mtp"] = ref._sequence_nll(xm[:, :-1], tokens[:, 2:], head)
        total = total + assumed["mtp_weight"] * out["ce_mtp"]
        aux, routed = aux + aux_m, routed + 1
    out["aux"] = aux / routed
    out["loss"] = total + assumed["balance_coefficient"] * out["aux"]
    return out


def loss_and_grads(params, tokens, cfg_file: Dict[str, Any]):
    """(loss, its gradient by ``jax.grad`` through the reference, float32
    leaf for leaf as ``params``'s tree)."""
    import jax
    import jax.numpy as jnp

    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return jax.value_and_grad(
        lambda p: loss(p, tokens, cfg_file)["loss"])(params)


# ---- the arithmetic ------------------------------------------------------------

def mla_matmul_params(hf: Dict[str, Any]) -> int:
    """One mixer's matrices: the query's two factors, the down- and
    up-projection and o."""
    d, H, r = hf["hidden_size"], hf["num_attention_heads"], hf["kv_lora_rank"]
    nope, rope, dv = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                      hf["v_head_dim"])
    rq = hf["q_lora_rank"]
    return (d * rq + rq * H * (nope + rope) + d * (r + rope)
            + r * H * (nope + dv) + H * dv * d)


def hyper_matmul_params(hf: Dict[str, Any]) -> int:
    """A layer's two ``phi``."""
    n = hf["hc_mult"]
    return 2 * n * hf["hidden_size"] * (n * n + 2 * n)


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the matrix multiplications of the ``n_layers`` layers
    and of the prediction module (norms, biases and scalars left out): a
    mixer, a layer's two ``phi``, a dense SwiGLU or the shared expert, the
    router at its published width and the routed experts held here, or
    (``active_only``) the visits a token pays them on average
    (``num_experts_per_tok * held / published`` experts' worth); the module
    is one more expert layer and its projection ``[2 d, d]``, and with
    ``active_only`` the head once more, which it multiplies by too."""
    d, f = hf["hidden_size"], hf["moe_intermediate_size"]
    published = _published_experts(hf)
    routed = (hf["num_experts_per_tok"] * hf["n_routed_experts"] / published
              if active_only else hf["n_routed_experts"])
    dense = dense_layers_run(hf, n_layers)
    sparse = (hf["n_shared_experts"] + routed) * 3 * d * f + d * published
    layer = mla_matmul_params(hf) + hyper_matmul_params(hf)
    modules = hf["num_nextn_predict_layers"]
    module = layer + sparse + 2 * d * d + (
        d * hf["vocab_size"] if active_only else 0)
    return int(n_layers * layer + dense * 3 * d * hf["intermediate_size"]
               + (n_layers - dense) * sparse + modules * module)


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Multiply-adds of the mixers' own products (no projection) for one
    token of a ``seq``-token sequence, forward: scores at 192 and values at
    128 over the ``seq / 2`` keys a query sees on average, every layer and
    the module's; counted like a matrix's parameters."""
    mla = hf["num_attention_heads"] * (
        hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"] + hf["v_head_dim"]
    ) * seq / 2.0
    return (n_layers + hf["num_nextn_predict_layers"]) * mla


def hyper_stream_bytes_per_token(hf: Dict[str, Any], n_layers: int,
                                 itemsize: int = 2) -> int:
    """Bytes a trained token's hyper-connections move by the least passes
    over the stream, forward and backward, over the ``n_layers`` layers' 2
    halves each (the module's lie under its own scope and are not counted
    here), whatever implements them, n the rows and d their width: forward
    the mix-in reads the rows and writes h, the mix-out reads the rows and
    the branch and writes the rows, ``(3 n + 2) d``; backward the mix-out's
    reads the rows' cotangent, the rows and the branch and writes the
    branch's cotangent, the mix-in's reads h's cotangent, the rows and the
    rows' cotangent again and writes the rows' cotangent, ``(5 n + 3) d``.
    The coefficients (24 a token) and a pass that remat runs again are not
    counted."""
    n, d = hf["hc_mult"], hf["hidden_size"]
    return 2 * n_layers * (8 * n + 5) * d * itemsize


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step would read of one cached position: the latent and
    the shared rotated key columns of every layer (no cell serves this
    family)."""
    return n_layers * (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize
