"""The dense family: pre-norm decoder blocks of grouped-query attention and
a SwiGLU feed-forward, as Mistral and Llama publish them and
``ray_tpu/models/llama.py`` computes them. Configuration keys are those of
their ``config.json``. Importing this file imports neither JAX nor the
program; its functions do."""

from typing import Any, Dict, Optional, Tuple

from benchmark.lib import arithmetic


# ---- the program's config and weights ----------------------------------------

def config_fields(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                  attn_impl: str = "xla", loss_chunk: int = 0) -> Dict[str, Any]:
    """``LlamaConfig``'s fields at the published widths, ``n_layers`` deep,
    bf16 parameters (the type the weights are served and trained in)."""
    import jax.numpy as jnp

    hf = cfg_file["config"]
    if hf.get("sliding_window") is not None:
        raise ValueError("the program has no windowed attention")
    return dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk)


def program_config(cfg_file: Dict[str, Any], n_layers: int, **how: Any):
    from ray_tpu.models import llama

    return llama.LlamaConfig(**config_fields(cfg_file, n_layers, **how))


def init_params(rng, cfg):
    from ray_tpu.models import llama

    return llama.init_params(rng, cfg)


# ---- the plain reference ----------------------------------------------------

ATTENTION_KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
                  "rope_theta")


def _static(cfg_file: Dict[str, Any]) -> Tuple:
    return tuple((k, cfg_file["config"][k]) for k in ATTENTION_KEYS)


def _block(x, layer, hf):
    from benchmark.lib import reference as ref

    x = ref.attention(x, layer, hf)
    h = ref.rms(x, layer["mlp_norm"], hf["rms_norm_eps"])
    return (x + ref.swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]),
            ref.F32(0))


def logits(params, tokens, cfg_file: Dict[str, Any]):
    """Float32 logits [b, s, V]."""
    from benchmark.lib import reference

    return reference.logits(params, tokens, _block, _static(cfg_file))


def token_margins(params, tokens, following, cfg_file: Dict[str, Any],
                  rows: Optional[Tuple[int, int]] = None):
    """``reference.token_margins``: an entry per position of ``tokens``.
    ``rows`` = (first, end) are the positions the caller will read; at these
    lengths every row is computed and ``rows`` changes nothing."""
    from benchmark.lib import reference

    return reference.token_margins(params, tokens, following, _block,
                                   _static(cfg_file))


def loss(params, tokens, cfg_file: Dict[str, Any]):
    """Next-token cross entropy of tokens [b, s+1]."""
    from benchmark.lib import reference

    out = reference.loss(params, tokens, _block, _static(cfg_file))
    return {"loss": out["ce"], **out}


# ---- the arithmetic ------------------------------------------------------------

def attention_matmul_params(hf: Dict[str, Any]) -> int:
    """Parameters of one layer's four attention projections."""
    d = hf["hidden_size"]
    q = hf["num_attention_heads"] * arithmetic.head_dim(hf)
    kv = hf["num_key_value_heads"] * arithmetic.head_dim(hf)
    return d * q + 2 * d * kv + q * d


def matmul_params(hf: Dict[str, Any], n_layers: int, active_only: bool = True) -> int:
    """Parameters of the layers' matrix multiplications (norms left out)."""
    mlp = 3 * hf["hidden_size"] * hf["intermediate_size"]
    return n_layers * (attention_matmul_params(hf) + mlp)


def attention_flops_per_token(hf: Dict[str, Any], n_layers: int, seq: int) -> float:
    """Multiply-adds of the score and value products for one token of a
    ``seq``-token causal sequence, forward, at half the square: counted like
    a matrix's parameters, 6 operations each forward and backward."""
    return n_layers * hf["num_attention_heads"] * arithmetic.head_dim(hf) * seq


def cache_bytes_per_position(hf: Dict[str, Any], n_layers: int,
                             itemsize: int = 2) -> int:
    """What a decode step reads of one cached position: keys and values."""
    return 2 * n_layers * hf["num_key_value_heads"] * arithmetic.head_dim(hf) * itemsize
