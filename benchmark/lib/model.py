"""From a configuration file to the program's own config object, and the
device as the process that holds it sees it. Runs in the worker that was
granted the chip; the parent never imports JAX."""

from __future__ import annotations

import os
from typing import Any, Dict


def program_config(cfg_file: Dict[str, Any], n_layers: int, *, max_seq_len: int,
                   attn_impl: str = "xla", loss_chunk: int = 0):
    """``LlamaConfig`` / ``MoEConfig`` at the published widths, ``n_layers``
    deep, bf16 parameters (the type the weights are served and trained in)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe

    hf = cfg_file["config"]
    if hf.get("sliding_window") is not None:
        raise ValueError("the program has no windowed attention")
    common = dict(
        vocab_size=hf["vocab_size"], d_model=hf["hidden_size"],
        n_layers=n_layers, n_heads=hf["num_attention_heads"],
        n_kv_heads=hf["num_key_value_heads"], d_ff=hf["intermediate_size"],
        max_seq_len=max_seq_len, rope_theta=float(hf["rope_theta"]),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf["tie_word_embeddings"]),
        param_dtype=jnp.bfloat16, attn_impl=attn_impl, loss_chunk=loss_chunk)
    if cfg_file["family"] == "dense":
        return llama.LlamaConfig(**common)
    if cfg_file["family"] == "moe":
        return moe.MoEConfig(
            **common, n_experts=hf["num_local_experts"],
            top_k=hf["num_experts_per_tok"],
            capacity_factor=float(cfg_file["assumed"]["capacity_factor"]),
            router_aux_coef=float(hf["router_aux_loss_coef"]))
    raise ValueError(f"no model family {cfg_file['family']!r}")


def device_facts() -> Dict[str, Any]:
    """Platform, kind, count and the peak memory of the fullest chip."""
    import jax

    devices = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    limit = (devices[0].memory_stats() or {}).get("bytes_limit", 0)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks)),
            "memory_limit_bytes": int(limit), "pid": os.getpid(),
            "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
