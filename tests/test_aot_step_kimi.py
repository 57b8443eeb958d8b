"""Kimi-Linear's step, the cell's whole depth at the cell's shape, on one
described chip.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows); this one
holds one compiled step and the tests that read it.
"""

import re

import jax.numpy as jnp
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.pallas import flash
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.util import hlo_copies

import _aot
from _aot import _compile_fused_step, compiled_kernel, topo  # noqa: F401

STEP = "kimi-kda-mla"

# Kimi-Linear-48B-A3B-Instruct at its published widths as its cell trains it
# (benchmark/configs/kimi-linear-48b-a3b-instruct.json: 8 of 256 experts and
# an eighth of the vocabulary held here), the cell's whole depth: the leading
# dense KDA layer and the period KDA, KDA, KDA, MLA
CFG_KIMI = moe.MoEConfig(
    vocab_size=20480, d_model=2304, n_layers=5, n_heads=32, n_kv_heads=32,
    attn_head_dim=72, d_ff=1024, d_ff_dense=9216, max_seq_len=16384,
    tie_embeddings=False, param_dtype=jnp.bfloat16, attn_impl="flash",
    loss_chunk=256, layer_kinds=("kda",) * 4 + ("mla",), n_dense_layers=1,
    n_experts=256, n_experts_held=8, top_k=8, n_shared_experts=1,
    router_score="sigmoid", router_bias=True, route_scale=2.446,
    balance="sequence", router_aux_coef=0.0, kda_heads=32, kda_head_dim=128,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128)


@pytest.fixture(scope="module")
def kimi_step(topo):
    """``CFG_KIMI``, b1 x s16384, K=1 on one described chip: (K, batch, seq,
    compiled)."""
    mesh = make_mesh(MeshConfig(), topo.devices[:1])
    return 1, 1, 16384, _compile_fused_step(moe, CFG_KIMI, mesh, 1, 1,
                                            16384)[2]


def test_kimi_linears_step_compiles_for_one_v5e_at_the_cells_shape(kimi_step,
                                                                   capsys):
    """b1 x s16384, K=1, all five layers on one described chip: Mosaic takes
    the flash kernels at 192 / 128 (192 is no multiple of the lanes), the
    step fits the chip's 15.75 GiB with room (what the chunked delta rule
    keeps for its backward is a state a segment, and a segment's temporaries
    are live at once, not the sequence's), and the backward runs no second
    flash forward."""
    compiled = kimi_step[-1]
    text = compiled.as_text()
    customs = [line for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    calls = [re.search(r"flash_(fwd|dq|dkv)_bh32_q16384_k16384_d192v128_c1_w0",
                       line) for line in customs if "kda_" not in line]
    assert all(calls) and sorted(m.group(1) for m in calls) \
        == sorted(flash.KINDS), calls
    # everything of the four KDA layers' chunks that does not read the state
    # is the kernel pair's, a segment of 8 chunks a call: forward, and
    # backward once more forward (the segment rebuilt from the state it
    # started with) and the one backward call
    insides = [re.search(r"kda_insides_(fwd|bwd)_bh32_n8_c64_k128_v128", line)
               for line in customs if "kda_insides" in line]
    assert all(insides) and sorted(m.group(1) for m in insides) \
        == ["bwd"] * 4 + ["fwd"] * 8, insides
    assert not any("kda_grams" in line for line in customs)
    # and their chains round the recurrence the kernels of
    # ``ops/pallas/kda_mix.py``: a layer's q and k (``conv_unit``), v
    # (``conv``), decay (``decay``) and output (``norm_gate``), each forward, once more forward
    # inside the backward (a remat block keeps the products' results, not
    # the chains') and once backward
    mixes = [re.search(r"kda_mix_(\w+)_s16384_h32_w128", line)
             for line in customs if "kda_mix" in line]
    assert all(mixes), mixes
    counts = {name: sum(m.group(1) == name for m in mixes)
              for name in {m.group(1) for m in mixes}}
    assert counts == {"conv_unit_fwd": 16, "conv_unit_bwd": 8, "conv_fwd": 8,
                      "conv_bwd": 4, "decay_fwd": 8, "decay_bwd": 4,
                      "norm_gate_fwd": 8, "norm_gate_bwd": 4}
    # no float32 stream is re-laid or spread through HBM outside the
    # recurrence (the parent's step made twelve such copies, a layer's decay
    # turned heads first forward, recomputed and backward; a layer alone
    # with a stand-in for the recurrence, ISSUE 51's reading, also the
    # [16384, 32] norms' spread over a head's channels)
    streams = {("f32", dims) for dims in (
        (16384, 32, 128), (1, 16384, 32, 128), (16384, 4096), (1, 16384, 4096))}
    spread = [(inst[2], inst[1]) for _, inst, _ in
              hlo_copies._Module(text).walk()
              if inst[2] in ("broadcast", "copy") and "kda_scan" not in inst[4]
              and set(hlo_copies._arrays(inst[1])) & streams]
    assert not spread, spread
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nkimi-linear b1 x s16384: temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, peak "
              f"{mem.peak_memory_in_bytes / 2**30:.2f} GiB")
    # 11.52 at PR 60 as at PR 54 (11.80 at PR 53, 12.36 at PR 48): the loss's
    # rule (``llama._looped_ce``) carries the head's gradient in the head's
    # dtype, and a group's kept cotangent, 84 MB here, is not where the step
    # peaks. The cells this file does not compile whole read the same on the
    # described chip with and without the rule: Mistral's six layers 14.71
    # GiB, Trinity's 15.06 (a float32 carry: 14.90 and 15.20)
    assert mem.peak_memory_in_bytes < 13.0 * 2**30
    assert mem.argument_size_in_bytes > 3.3 * 2**30   # 602M x 6 bytes


def test_a_train_step_names_all_of_itself(kimi_step, capsys):
    k, _, seq, compiled = kimi_step
    _aot.names_all_of_itself(
        STEP, compiled, {
            "embed", "attn_kda", "attn_mla", "mlp", "moe_router",
            "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
            "loss_head", "optimizer"}, 140, 155, CFG_KIMI, k, seq, 1, capsys)
