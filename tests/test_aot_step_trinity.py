"""Trinity-Large's fused step, one banded and one full layer, on one
described chip.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows); this one
holds one compiled step and the tests that read it.
"""

import math

import jax.numpy as jnp
import pytest

from ray_tpu.models import moe
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

import _aot
from _aot import _compile_fused_step, compiled_kernel, topo  # noqa: F401

STEP = "trinity-window-full"

# Trinity-Large-Preview at its published widths as its cell trains it
# (benchmark/configs/trinity-large-preview.json: 8 of 256 experts and an
# eighth of the vocabulary held here), cut to one banded and one full layer
CFG_TRINITY = moe.MoEConfig(
    vocab_size=25024, d_model=3072, n_layers=2, n_heads=48, n_kv_heads=8,
    attn_head_dim=128, d_ff=3072, max_seq_len=8192, rope_theta=1e4,
    tie_embeddings=False, param_dtype=jnp.bfloat16, attn_impl="flash",
    loss_chunk=256, qk_norm_head=True, attn_gate=True, sandwich_norm=True,
    embedding_multiplier=math.sqrt(3072), layer_kinds=("window", "full"),
    sliding_window=4096, n_experts=256, n_experts_held=8, top_k=4,
    n_shared_experts=1, router_score="sigmoid", router_bias=True,
    route_scale=2.448, balance="sequence", router_aux_coef=5e-5)


@pytest.fixture(scope="module")
def trinity_step(topo):
    """``CFG_TRINITY``, b1 x s8192, K=2 on one described chip: (K, batch,
    seq, compiled)."""
    k, batch, seq = 2, 1, 8192
    mesh = make_mesh(MeshConfig(), topo.devices[:1])
    return k, batch, seq, _compile_fused_step(moe, CFG_TRINITY, mesh, k,
                                              batch, seq)[2]


def test_the_backward_runs_no_second_forward(trinity_step, capsys):
    _aot.runs_no_second_forward(STEP, trinity_step[-1], 2, 3664176128,
                                8189170688, capsys)


def test_a_train_step_names_all_of_itself(trinity_step, capsys):
    k, _, seq, compiled = trinity_step
    _aot.names_all_of_itself(
        STEP, compiled, {
            "embed", "attn_window", "attn_full", "moe_router", "moe_dispatch",
            "moe_experts", "moe_combine", "moe_shared", "loss_head",
            "optimizer"}, 45, 75, CFG_TRINITY, k, seq, 1, capsys)
