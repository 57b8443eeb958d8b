"""The moe family: the dense family's attention with a sparse feed-forward,
a softmax router whose top-k gates are renormalised, as Mixtral publishes it
and ``ray_tpu/models/moe.py`` computes it. Two departures, both the
program's training path and both set out in the configuration file's
``assumed``: tokens beyond an expert's capacity are dropped (queue order:
every token's first choice, then every token's second), and the balancing
loss counts first choices only. Importing this file imports neither JAX nor
the program; its functions do."""

from typing import Any, Dict, Optional, Tuple

from benchmark.lib import spec

_dense = spec.load_family("dense", spec.root_of(__file__))


# ---- the program's config and weights ----------------------------------------

def program_config(cfg_file: Dict[str, Any], n_layers: int, **how: Any):
    from ray_tpu.models import moe

    hf = cfg_file["config"]
    return moe.MoEConfig(
        **_dense.config_fields(cfg_file, n_layers, **how),
        n_experts=hf["num_local_experts"], top_k=hf["num_experts_per_tok"],
        capacity_factor=float(cfg_file["assumed"]["capacity_factor"]),
        router_aux_coef=float(hf["router_aux_loss_coef"]))


def init_params(rng, cfg):
    from ray_tpu.models import moe

    return moe.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

def _static(cfg_file: Dict[str, Any], capacity_factor: Optional[float]) -> Tuple:
    hf = cfg_file["config"]
    keys = _dense.ATTENTION_KEYS + ("num_local_experts", "num_experts_per_tok")
    return tuple((k, hf[k]) for k in keys) + (("capacity_factor", capacity_factor),)


def _experts(x, layer, hf: Dict[str, Any], capacity_factor: Optional[float]):
    import functools

    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference as ref

    b, s, d = x.shape
    n_exp, top_k = hf["num_local_experts"], hf["num_experts_per_tok"]
    h = ref.rms(x, layer["mlp_norm"], hf["rms_norm_eps"]).reshape(b * s, d)
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
    weight = jnp.einsum("gk,gke->ge", top_p, chosen.astype(ref.F32))
    y = jnp.zeros_like(h)
    for e in range(n_exp):
        one = functools.partial(ref.swiglu, gate=layer["e_gate"][e],
                                up=layer["e_up"][e], down=layer["e_down"][e])
        y = y + weight[:, e:e + 1] * ref.in_chunks(one, h)
    first = jnp.mean(chosen[:, 0, :].astype(ref.F32), axis=0)
    aux = n_exp * jnp.sum(first * jnp.mean(probs, axis=0))
    return x + y.reshape(b, s, d), aux


def _block(x, layer, hf):
    from benchmark.lib import reference as ref

    return _experts(ref.attention(x, layer, hf), layer, hf, hf["capacity_factor"])


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V], routing without drops, as Mixtral does."""
    from benchmark.lib import reference

    return reference.logits(params, tokens, _block, _static(cfg_file, None))


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """As the dense family's, routing without drops."""
    from benchmark.lib import reference

    return reference.token_margins(params, tokens, following, _block,
                                   _static(cfg_file, None))


def loss(params, tokens, cfg_file: Dict[str, Any]):
    """Next-token cross entropy of tokens [b, s+1] under the capacity that
    ``assumed`` sets (none: nothing drops), and the balancing loss weighted
    as the published config says."""
    from benchmark.lib import reference

    capacity = cfg_file["assumed"].get("capacity_factor")
    out = reference.loss(params, tokens, _block, _static(cfg_file, capacity))
    coef = cfg_file["config"]["router_aux_loss_coef"]
    return {"loss": out["ce"] + coef * out["aux"], **out}


# ---- the arithmetic ------------------------------------------------------------

def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms left out):
    the experts a token is routed to (``active_only``) or all of them, plus
    the router."""
    d, experts = hf["hidden_size"], hf["num_local_experts"]
    used = hf["num_experts_per_tok"] if active_only else experts
    return n_layers * (_dense.attention_matmul_params(hf)
                       + used * 3 * d * hf["intermediate_size"] + d * experts)


attention_flops_per_token = _dense.attention_flops_per_token
cache_bytes_per_position = _dense.cache_bytes_per_position
