"""The olmoe family: OLMoE's block as allenai publishes it (transformers'
``modeling_olmoe.py``) and ``ray_tpu/models/moe.py`` serves it with
``qk_norm`` on and ``norm_topk_prob`` as the config says. Against the moe
family it differs in two places:

- QK-norm: one learned RMSNorm over the whole q projection and one over the
  whole k projection, before the split into heads and before RoPE;
- the top-k gates are the float32 softmax's own values: with
  ``norm_topk_prob: false`` they are NOT divided by their sum.

Departures, all set out in the configuration file's ``assumed``: the
published config has no key for the QK-norm (it is in the model code) nor
for one expert's width (``intermediate_size`` is read as that width); the
balancing loss counts first choices only, as the program's training path
and the moe family's file do (no train cell runs this family, and routing
here drops nothing). Importing this file imports neither JAX nor the
program; its functions do."""

from typing import Any, Dict, Optional, Tuple

from benchmark.lib import spec

_dense = spec.load_family("dense", spec.root_of(__file__))


# ---- the program's config and weights ----------------------------------------

# what the program's config classes must have for this family, by the file
# that defines each
NEEDS = {"moe": "norm_topk_prob", "llama": "qk_norm"}


def require_program() -> None:
    """Raise ``spec.SpecError`` where the checkout's program cannot build
    this family's config (``ray_tpu/models`` before PR 26 has neither
    field). The cell's readers call it as the parent process loads them,
    before a replica is deployed: ``serve.run`` starts a replica whose
    constructor raises again and again, so a run on such a checkout would
    hang where it has to fail. Reads the source and imports nothing (the
    parent process stays off JAX)."""
    import os
    import re

    import ray_tpu

    models = os.path.join(os.path.dirname(ray_tpu.__file__), "models")
    for module, field in NEEDS.items():
        with open(os.path.join(models, module + ".py")) as f:
            if not re.search(rf"^\s+{field}\s*:", f.read(), re.M):
                raise spec.SpecError(
                    f"family olmoe needs the config field {field!r}, which "
                    f"{models}/{module}.py does not have: this checkout's "
                    f"program cannot run it")


def program_config(cfg_file: Dict[str, Any], n_layers: int, **how: Any):
    from ray_tpu.models import moe

    hf, assumed = cfg_file["config"], cfg_file["assumed"]
    return moe.MoEConfig(
        **_dense.config_fields(cfg_file, n_layers, **how),
        n_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        norm_topk_prob=bool(hf["norm_topk_prob"]), qk_norm=True,
        capacity_factor=float(assumed.get("capacity_factor", 1.25)),
        router_aux_coef=float(assumed.get("router_aux_loss_coef", 0.01)))


def init_params(rng, cfg):
    from ray_tpu.models import moe

    return moe.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

def _static(cfg_file: Dict[str, Any]) -> Tuple:
    hf = cfg_file["config"]
    keys = _dense.ATTENTION_KEYS + ("num_experts", "num_experts_per_tok",
                                    "norm_topk_prob")
    return tuple((k, hf[k]) for k in keys)


def _attention(x, layer, hf: Dict[str, Any]):
    """The attention half, residual included: q and k normed whole, then
    split into heads and rotated; plain causal attention, one key/value
    head (and the query heads that share it) at a time."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, _ = x.shape
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd, eps = layer["wq"].shape[-1] // hq, hf["rms_norm_eps"]
    h = ref.rms(x, layer["attn_norm"], eps)
    q = ref.rms(h @ layer["wq"], layer["q_norm"], eps).reshape(b, s, hq, hd)
    k = ref.rms(h @ layer["wk"], layer["k_norm"], eps).reshape(b, s, hkv, hd)
    v = (h @ layer["wv"]).reshape(b, s, hkv, hd)
    q, k = ref.rope(q, hf["rope_theta"]), ref.rope(k, hf["rope_theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(qkv):
        qg, kg, vg = qkv  # [b, s, hq/hkv, hd], [b, s, hd], [b, s, hd]
        scores = jnp.einsum("bqgd,bkd->bgqk", qg, kg) / jnp.sqrt(ref.F32(hd))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", p, vg)

    qg = q.reshape(b, s, hkv, hq // hkv, hd).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(group, (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, s, hq * hd)
    return x + out @ layer["wo"]


def _experts(x, layer, hf: Dict[str, Any]):
    """Every expert on every token, weighted by the token's gate for it (0
    where it was not among the top k): no capacity, nothing dropped."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, d = x.shape
    n_exp, top_k = hf["num_experts"], hf["num_experts_per_tok"]
    h = ref.rms(x, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
    probs = jax.nn.softmax(h @ layer["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    if hf["norm_topk_prob"]:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-9)
    chosen = jax.nn.one_hot(top_i, n_exp, dtype=ref.F32)  # [G, K, E]
    weight = jnp.einsum("gk,gke->eg", top_p, chosen)

    def add(y, expert):
        gate, up, down, w = expert
        one = lambda t: ref.swiglu(t, gate, up, down)  # noqa: E731
        return y + w[:, None] * ref.in_chunks(one, h), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), (
        layer["e_gate"], layer["e_up"], layer["e_down"], weight))
    first = jnp.mean(chosen[:, 0, :], axis=0)
    aux = n_exp * jnp.sum(first * jnp.mean(probs, axis=0))
    return x + y.reshape(b, s, d), aux


def _block(x, layer, hf):
    return _experts(_attention(x, layer, hf), layer, hf)


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V]."""
    from benchmark.lib import reference

    return reference.logits(params, tokens, _block, _static(cfg_file))


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's: every row is computed, ``rows`` changes nothing."""
    from benchmark.lib import reference

    return reference.token_margins(params, tokens, following, _block,
                                   _static(cfg_file))


def loss(params, tokens, cfg_file: Dict[str, Any]):
    """Next-token cross entropy of tokens [b, s+1] without drops, and the
    first-choice balancing loss under the coefficient ``assumed`` gives."""
    from benchmark.lib import reference

    out = reference.loss(params, tokens, _block, _static(cfg_file))
    coef = float(cfg_file["assumed"].get("router_aux_loss_coef", 0.01))
    return {"loss": out["ce"] + coef * out["aux"], **out}


# ---- the arithmetic ------------------------------------------------------------

def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms left out):
    the experts a token is routed to (``active_only``) or all of them, plus
    the router. ``intermediate_size`` is one expert's width."""
    d, experts = hf["hidden_size"], hf["num_experts"]
    used = hf["num_experts_per_tok"] if active_only else experts
    return n_layers * (_dense.attention_matmul_params(hf)
                       + used * 3 * d * hf["intermediate_size"] + d * experts)


attention_flops_per_token = _dense.attention_flops_per_token
cache_bytes_per_position = _dense.cache_bytes_per_position
