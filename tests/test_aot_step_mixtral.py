"""Mixtral-8x7B's fused step over ``fsdp 4`` on four described chips.

One of the files that ask the chip's compiler, without the chip
(``test_aot_tpu_compile.py``'s docstring says what that shows); this one
holds one compiled step and the tests that read it.
"""

import math

import jax.numpy as jnp
import pytest

from ray_tpu.models import moe
from ray_tpu.parallel import train_step as ts
from ray_tpu.util import hlo_copies

import _aot
from _aot import _compile_fused_step, compiled_kernel, topo  # noqa: F401

STEP = "mixtral-fsdp4"

# Mixtral-8x7B at its published widths (benchmark/configs/
# mixtral-8x7b-v0.1.json) as its four-chip cell trains it, one layer deep
CFG_MIXTRAL = moe.MoEConfig(
    vocab_size=32000, d_model=4096, n_layers=1, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq_len=4096, rope_theta=1e6, tie_embeddings=False,
    param_dtype=jnp.bfloat16, attn_impl="flash", loss_chunk=256,
    n_experts=8, top_k=2, capacity_factor=1.25, router_aux_coef=0.02)


@pytest.fixture(scope="module")
def mixtral_step(topo):
    """``CFG_MIXTRAL``, b4 x s4096, K=2 over ``fsdp 4``, as the four-chip
    cell runs it: (K, batch, seq, compiled)."""
    k, batch, seq = 2, 4, 4096
    mesh, _ = ts.auto_mesh(4, topo.devices, tp=1)
    plan, p_sh, compiled = _compile_fused_step(moe, CFG_MIXTRAL, mesh, k,
                                               batch, seq)
    assert plan.expert_placement() == "expert"
    assert "fsdp" in p_sh["layers"]["e_gate"].spec[1]
    assert p_sh["layers"]["e_gate"].spec[2] is None
    return k, batch, seq, compiled


def test_mixtral_step_keeps_its_experts_rows_on_their_chip(mixtral_step,
                                                           capsys):
    """Eight experts split four ways, so a chip owns two whole experts and
    contracts the whole model dim. No collective of the compiled step is
    then an ``[E, C, f]`` buffer (under fsdp on the model dim there were
    five, 1.17 GB each: every chip's partial products summed onto every
    chip) and none completes a product of ``moe_experts``; what crosses
    chips in the layer is ``[E, C, d]``."""
    cfg = CFG_MIXTRAL
    _, batch, seq, compiled = mixtral_step
    E = cfg.n_experts
    C = int(cfg.capacity_factor * batch * seq * cfg.top_k / E)
    found = hlo_copies.collectives(compiled)
    assert found
    for c in found:
        assert "moe_experts" not in c["op_name"], c
        for _, dims in c["arrays"]:
            assert math.prod(dims) != E * C * cfg.d_ff, c
    with capsys.disabled():
        print("\nMixtral, 1 layer, fsdp 4, collectives a launch of 2 steps:")
        for kind, n in hlo_copies.collective_inventory(compiled).items():
            print(f"  {kind}: {n['count']} ({n['runs']} runs), "
                  f"{n['bytes'] / 1e9:.2f} GB of results")


def test_mixtral_step_moves_its_rows_by_index(mixtral_step, capsys):
    """Dispatch and combine are row gathers by index (``moe._dispatch``,
    ``moe._combine``): the compiled step holds no ``[G, E, C]`` array, whole
    or a chip's share, and no matrix product under ``moe_dispatch`` or
    ``moe_combine``; what those scopes move across chips is the tokens
    gathered to their experts' owners and the partial outputs
    reduce-scattered back, never more than ``[G, d]`` (the ``[E, C, d]``
    exchange is gone); and no ``d``-wide row is scattered: each move's
    backward is the other move. Fails on the parent, where the rows moved
    through ``gd,gec->ecd`` and ``ecd,gec->gd`` against one-hot tensors."""
    cfg = CFG_MIXTRAL
    _, batch, seq, compiled = mixtral_step
    G, E, d = batch * seq, cfg.n_experts, cfg.d_model
    C = int(cfg.capacity_factor * G * cfg.top_k / E)
    scopes = ("moe_dispatch", "moe_combine")
    for _, (name, shape, opcode, _, line), _ in hlo_copies._Module(
            compiled.as_text()).walk(fusions=True):
        arrays = hlo_copies._arrays(shape)
        for _, dims in arrays:
            assert math.prod(dims) not in (G * E * C, G // 4 * E * C), line[:300]
        source = hlo_copies._OP_NAME.search(line)
        if not (source and any(s in source.group(1) for s in scopes)):
            continue
        assert opcode not in ("dot", "convolution"), line[:300]
        assert not (opcode == "fusion" and "convolution" in name), line[:300]
        if opcode == "scatter":
            assert all(dims[-1:] != (d,) for _, dims in arrays), line[:300]
    every = hlo_copies.collectives(compiled)
    found = [c for c in every if any(s in c["op_name"] for s in scopes)]
    with capsys.disabled():
        print("\nMixtral, 1 layer, fsdp 4, collectives under moe_dispatch and "
              "moe_combine, a launch of 2 steps:")
        for c in found:
            if c["bytes"] >= 2 ** 20:  # (indices and gates are 0.1-0.3 MB)
                print(f"  {c['kind']} {c['arrays']} x{c['runs']}, "
                      f"{c['runs'] * c['bytes'] / 1e6:.1f} MB  {c['op_name']}")
    # the tokens' gather: forward, rematted, and for combine's backward
    # (the reduce-scatters back are merged with small ones by the compiler
    # and lose their op_name: they are held by size below, with all others)
    gathers = [c for c in found if c["kind"] == "all-gather"
               and any(math.prod(dims) == G * d for _, dims in c["arrays"])]
    assert len(gathers) == 3, gathers
    for c in found:
        for _, dims in c["arrays"]:
            assert math.prod(dims) <= G * d, c
    for c in every:
        for _, dims in c["arrays"]:
            assert math.prod(dims) not in (E * C * d, E * C * d // 4), c


def test_the_loss_gathers_its_head_once_a_step(mixtral_step, capsys):
    k, _, seq, compiled = mixtral_step
    _aot.holds_the_head_once_a_step(STEP, compiled, CFG_MIXTRAL, k, seq, 1,
                                    capsys)


def test_the_backward_runs_no_second_forward(mixtral_step, capsys):
    _aot.runs_no_second_forward(STEP, mixtral_step[-1], 1, 2467869184,
                                4975883776, capsys)


def test_a_train_step_names_all_of_itself(mixtral_step, capsys):
    k, _, seq, compiled = mixtral_step
    _aot.names_all_of_itself(
        STEP, compiled, {
            "embed", "attn_full", "moe_router", "moe_dispatch", "moe_experts",
            "moe_combine", "loss_head", "optimizer"}, 8, 60, CFG_MIXTRAL, k,
        seq, 1, capsys)
