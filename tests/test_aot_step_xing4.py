"""Xing4.0's step, the cell's whole depth and its prediction module at the
cell's shape, on one described chip.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows); this one
holds one compiled step and the tests that read it.
"""

import collections
import re

import jax.numpy as jnp
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.pallas import flash
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

from _aot import (_assert_three_products_a_chunk,  # noqa: F401 (fixtures)
                  _compile_fused_step, _scopes_of_a_step, compiled_kernel,
                  topo)


# Xing4.0-29B-A4B at its published widths as its cell trains it
# (benchmark/configs/xing4.0-29b-a4b.json: 8 of 64 experts and an eighth of
# the vocabulary held here), the cell's whole depth and its prediction module
def _cfg_xing4():
    from ray_tpu.ops.rope import Yarn

    return moe.MoEConfig(
        vocab_size=16384, d_model=3584, n_layers=5, n_heads=32, n_kv_heads=32,
        d_ff=1024, d_ff_dense=9216, max_seq_len=8192, norm_eps=1e-6,
        tie_embeddings=False, param_dtype=jnp.bfloat16, attn_impl="flash",
        loss_chunk=256, layer_kinds=("mla",) * 5, n_dense_layers=1,
        n_experts=64, n_experts_held=8, top_k=4, n_shared_experts=1,
        router_score="sigmoid", router_bias=True, route_scale=2.0,
        balance="sequence", router_aux_coef=0.0, kv_lora_rank=512,
        q_lora_rank=768, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, mla_rope=True,
        mla_yarn=Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0), hc_mult=4,
        n_mtp_modules=1)


@pytest.fixture(scope="module")
def xing4_step(topo):
    """The cell's config, b1 x s8192, K=2 on one described chip: (K, batch,
    seq, compiled)."""
    mesh = make_mesh(MeshConfig(), topo.devices[:1])
    return 2, 1, 8192, _compile_fused_step(moe, _cfg_xing4(), mesh, 2, 1,
                                           8192)[2]


def test_xing4s_step_compiles_for_one_v5e_at_the_cells_shape(xing4_step,
                                                             capsys):
    """b1 x s8192, K=2, five layers and the prediction module on one
    described chip, every layer its own remat block over a four-row stream:
    Mosaic takes the flash kernels at 192 / 128 with 32 heads, six layers'
    worth and no second forward, and the step fits the chip's 15.75 GiB
    beside 913.6M parameters' state (8 bytes each: the arguments are 6)."""
    compiled = xing4_step[-1]
    customs = [line for line in compiled.as_text().splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    calls = [re.search(r"flash_(fwd|dq|dkv)_bh32_q8192_k8192_d192v128_c1_w0"
                       r"|mhc_(in|out)_(fwd|bwd)_n4_t8192_d3584", line)
             for line in customs]
    assert all(calls), [c for c, m in zip(customs, calls) if not m]
    count = collections.Counter(
        "_".join(filter(None, m.groups())) for m in calls)
    # the dense layer, the scanned expert layers' one body, the module's:
    # three layers' worth of the flash kernels and no second forward, and of
    # the hyper-connections' four calls (PR 57) a half layer one each way,
    # with the attention half's mix_out a second time in a layer's backward
    # (its rows are what the feed-forward half's backward reads; mix_in's
    # call, whose results the block keeps by name, runs once)
    assert count == {**{kind: 3 for kind in flash.KINDS}, "in_fwd": 6,
                     "out_fwd": 9, "out_bwd": 6, "in_bwd": 6}, count
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nxing4 b1 x s8192, K=2: temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, peak "
              f"{mem.peak_memory_in_bytes / 2**30:.2f} GiB")
    # 13.66 at PR 60: two looped cross entropies, each a group's cotangent
    # kept (67 MB) and its gradients held to the backward (13.56 at PR 57,
    # 13.32 at PR 56)
    assert mem.peak_memory_in_bytes < 15.75 * 2**30
    assert mem.argument_size_in_bytes > 5.0 * 2**30    # 913.6M x 6 bytes
    # and it names all of itself, as ``test_a_train_step_names_all_of_itself``
    # holds the older steps to (here and not a case of that test's: a case
    # may run on another worker and compile the step, ~110 s, a second time)
    named, stacking, rootless, strays = _scopes_of_a_step(compiled)
    assert not strays, strays
    assert set(named) == {
        "embed", "hyper_mix", "attn_mla", "mlp", "moe_router", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared", "mtp", "loss_head",
        "optimizer"} <= set(ts.STEP_SCOPES), named
    assert all(set(inside) <= set(ts.STEP_SCOPES)
               for *_, inside in rootless), rootless
    assert len(stacking) <= 140 and len(rootless) <= 95, (
        len(stacking), len(rootless))
    # both of its cross entropies, the main head's and the prediction
    # module's, are ``llama._looped_ce``'s loop: 32 chunks in 4 groups each
    for scope in ("loss_head", "mtp"):
        assert _assert_three_products_a_chunk(
            compiled, scope, 2, _cfg_xing4(), 8192) == (32, 4)
