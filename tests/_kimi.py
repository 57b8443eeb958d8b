"""What Kimi Linear's two test files share (``test_kimi_linear_training.py``:
the kernels and a layer; ``test_kimi_linear_model.py``: the program against
the reference): the family, its tiny config and the tokens. Imported, not
collected."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import spec  # noqa: E402

# config.json's keys at a tiny size: eight published layers, KDA KDA KDA MLA
# twice, of which the leading dense one and the second period run
TINY = {
    "first_k_dense_replace": 1, "head_dim": 8, "hidden_size": 32,
    "intermediate_size": 64, "kv_lora_rank": 16,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "head_dim": 16,
        "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "moe_intermediate_size": 24, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 4,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_token": 2,
    "num_hidden_layers": 8, "layers_run": [1, 5, 6, 7, 8],
    "num_key_value_heads": 4, "num_shared_experts": 1, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "v_head_dim": 16, "vocab_size": 96}
CFG_FILE = {"config": TINY,
            "assumed": {"capacity_factor": 1.25, "balance_coefficient": 0.0}}
SEQ, DEPTH = 40, 5
TOKENS = jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0, 96)


@pytest.fixture(scope="module")
def family():
    return spec.load_family("moonshot_kimi_linear")


def _cfg(family, attn_impl="flash", depth=DEPTH):
    cfg = family.program_config(CFG_FILE, depth, max_seq_len=SEQ,
                                attn_impl=attn_impl, loss_chunk=8)
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32)


