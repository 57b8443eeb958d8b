"""EvaByte's step, four layers at the cell's shape, on one described chip.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows); this one
holds one compiled step and the tests that read it.
"""

import re

import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.util import hlo_copies

import _aot
from _aot import _compile_fused_step, compiled_kernel, topo  # noqa: F401

STEP = "evabyte"

# EvaByte at its published widths as its cell trains it
# (benchmark/configs/evabyte.json): the first four of 32 layers, the whole
# vocabulary of 320, eight prediction heads
CFG_EVABYTE = llama.LlamaConfig(
    vocab_size=320, d_model=4096, n_layers=4, n_heads=32, n_kv_heads=32,
    d_ff=11008, max_seq_len=16384, rope_theta=100000.0,
    param_dtype=jnp.bfloat16, attn_impl="flash", loss_chunk=256,
    attn_kind="eva", eva_window=2048, eva_chunk=16, norm_unit_offset=True,
    residual_f32=True, n_pred_heads=8)


@pytest.fixture(scope="module")
def evabyte_step(topo):
    """``CFG_EVABYTE``, b1 x s16384, K=1 on one described chip: (K, batch,
    seq, compiled)."""
    mesh = make_mesh(MeshConfig(), topo.devices[:1])
    return 1, 1, 16384, _compile_fused_step(llama, CFG_EVABYTE, mesh, 1, 1,
                                            16384)[2]


def test_evabytes_step_compiles_for_one_v5e_at_the_cells_shape(evabyte_step,
                                                               capsys):
    """b1 x s16384, K=1, four layers on one described chip: Mosaic takes
    EVA attention's four kernels with their scalar-prefetched lists of
    visits (eight windows of two 1,024-row blocks, summary blocks of 128),
    each carries the name ``benchmark/kernels/eva_attn.py`` costs it by, and
    beside them the pair that makes their operands of the projections'
    results (``ops/pallas/eva_mix.py``, PR 55): ONE forward and ONE backward
    call in the step. The backward runs no second forward kernel of either
    family (the remat block keeps ``o`` and ``lse`` under
    ``flash.RESIDUAL_NAMES`` and the pair's five results under
    ``eva.RESIDUAL_NAMES``) and so none of ``wq``, ``wk``, ``wv``'s products
    a second time. The step fits the chip's 15.75 GiB with the 0.6 GiB
    ISSUE 52 asked for to spare."""
    from benchmark.kernels import eva_attn as cost

    compiled = evabyte_step[-1]
    text = compiled.as_text()
    customs = [line.strip() for line in text.splitlines()
               if "tpu_custom_call" in line and " custom-call(" in line]
    mixes = [m.group(1) for m in (re.search(
        r"eva_mix_(fwd|bwd)_s16384_h32_d128_c16/pallas_call", line)
        for line in customs) if m]
    assert sorted(mixes) == ["bwd", "fwd"], customs
    shapes = [cost.call_shape(line) for line in customs
              if "eva_mix_" not in line]
    assert all(shapes) and len(shapes) + len(mixes) == len(customs), customs
    assert sorted(shapes) == sorted(
        [(kind, 32, 16384, 128, 2048, 16, 2)
         for kind in ("fwd", "dq", "dkv", "dsum")]), shapes
    # what the backward computes a second time under ``attn_eva``: the
    # product with ``wo`` (the block keeps ``o`` and rebuilds the stream
    # after the mixer from it, as at PR 52) and none of ``wq``, ``wk``,
    # ``wv``'s, whose results only the pair's forward call read
    again = {re.sub(r"\.clone\.\d+$", "", inst[0])
             for _, inst, _ in hlo_copies._Module(text).walk(fusions=True)
             if inst[2] == "convolution"
             and "rematted_computation/attn_eva" in inst[4]}
    assert len(again) == 1, again
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\nevabyte b1 x s16384, 4 layers: temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, peak "
              f"{mem.peak_memory_in_bytes / 2**30:.2f} GiB")
    # 15.01 at PR 60 as at PR 55 (14.89 at PR 52)
    assert mem.peak_memory_in_bytes < 15.15 * 2**30
    assert mem.argument_size_in_bytes > 4.5 * 2**30   # 821M x 6 bytes


def test_a_train_step_names_all_of_itself(evabyte_step, capsys):
    k, _, seq, compiled = evabyte_step
    _aot.names_all_of_itself(
        STEP, compiled, {"embed", "attn_eva", "mlp", "loss_head",
                         "optimizer"}, 45, 10, CFG_EVABYTE, k, seq, 1, capsys)
