"""The fused-K step of the ``1b`` widths over fsdp x tp on four described
chips.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows); this one
holds one compiled step and the tests that read it.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, make_mesh

import _aot
from _aot import _compile_fused_step, compiled_kernel, topo  # noqa: F401

STEP = "1b-fsdp2-tp2"


@pytest.fixture(scope="module")
def flash_step(topo):
    """The fused-K step over fsdp x tp on four described chips, "1b" widths,
    depth cut to two layers for the test's time: (cfg, K, compiled)."""
    cfg = dataclasses.replace(_aot.CFG_1B, param_dtype=jnp.bfloat16,
                              attn_impl="flash", loss_chunk=256, n_layers=2)
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), topo.devices)
    k = 2
    return cfg, k, _compile_fused_step(llama, cfg, mesh, k, 2, 2048)[2]


def test_sharded_flash_step_compiles_for_four_chips(flash_step):
    """The TPU compiler does not partition a Mosaic kernel;
    ``flash_attention_on_mesh`` runs it per shard, and the fused-K step
    must hold both the kernel and the collectives."""
    text = flash_step[2].as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "all-reduce" in text


def test_the_loss_gathers_its_head_once_a_step(flash_step, capsys):
    cfg, k, compiled = flash_step
    _aot.holds_the_head_once_a_step(STEP, compiled, cfg, k, 2048, 2, capsys)


def test_the_backward_runs_no_second_forward(flash_step, capsys):
    _aot.runs_no_second_forward(STEP, flash_step[-1], 1, 449518080,
                                657651712, capsys)


def test_a_train_step_names_all_of_itself(flash_step, capsys):
    cfg, k, compiled = flash_step
    _aot.names_all_of_itself(
        STEP, compiled, {"embed", "attn_full", "mlp", "loss_head",
                         "optimizer"}, 55, 36, cfg, k, 2048, 2, capsys)
