"""Ask the chip's compiler, without the chip.

The TPU compiler is installed wherever libtpu is, and it compiles for a chip
that is described and not attached (``jax.experimental.topologies``). These
tests compile the kernels and programs of the main path at the ``"1b"``
(TinyLlama-1.1B) widths for a described ``v5e:2x2``: what interpret mode on
the CPU cannot show — a block the tiling refuses, too much VMEM, a Mosaic
kernel GSPMD cannot partition, a libtpu flag that aborts the process — fails
here at no chip time. Nothing runs: a compile that passes is not a chip run.

The file's name sorts first so that tier-1 reaches it inside its time limit.
"""

import dataclasses
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama, serving
from ray_tpu.ops.pallas import flash
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.context import mesh_scope
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.plan import compile_plan

CFG_1B = llama.PRESETS["1b"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def compiled_kernel(monkeypatch):
    """The process's backend is the CPU, where ``flash`` picks interpret
    mode; these tests are about the Mosaic kernel."""
    monkeypatch.setattr(flash, "_needs_interpret", lambda: False)


def _on(sharding, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("b,s,h,hkv,d", [
    (8, 2048, 16, 16, 64),    # the 410m widths
    (4, 2048, 32, 4, 64),     # "1b": grouped-query, 32 heads over 4
    (2, 2048, 32, 32, 128),   # head_dim 128
])
def test_flash_fwd_bwd_compiles_for_v5e(topo, b, s, h, hkv, d):
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # forward, dq, dk/dv
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_flash_with_traced_offset_compiles_for_v5e(topo):
    """``parallel/context.py``'s ring attention slides the causal mask with
    a traced ``q_offset`` (an SMEM scalar)."""
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 1024, 8, 64), jnp.bfloat16, sharding=one)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    compiled = jax.jit(lambda q, k, v, o: flash.flash_attention_with_lse(
        q, k, v, causal=True, q_offset=o)).lower(q, q, q, off).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _engine_args(topo, slots, max_len):
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), CFG_1B)))
    cache = jax.ShapeDtypeStruct(
        (CFG_1B.n_layers, slots, max_len, CFG_1B.n_kv_heads,
         CFG_1B.head_dim), CFG_1B.compute_dtype, sharding=one)
    return params, cache, lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one)


def test_engine_prefill_compiles_for_v5e_at_1b(topo):
    params, cache, i32 = _engine_args(topo, 8, 2048)
    compiled = serving._compiled_slot_prefill(CFG_1B, 1024, 8, 2048).lower(
        params, cache, cache, i32(1, 1024), i32()).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16 * 2 ** 30


def test_engine_decode_compiles_for_v5e_at_1b(topo):
    """Eight slots, eight fused decode steps: the engine's widest launch."""
    params, cache, i32 = _engine_args(topo, 8, 2048)
    compiled = serving._compiled_bucket_scan(CFG_1B, 8, 8, 2048, 8).lower(
        params, cache, cache, i32(8), i32(8), i32(8)).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < 16 * 2 ** 30


def test_sharded_flash_step_compiles_for_four_chips(topo):
    """fsdp x tp over four described chips, "1b" widths, depth cut to two
    layers for the test's time. The TPU compiler does not partition a Mosaic
    kernel; ``flash_attention_on_mesh`` runs it per shard, and the fused-K
    step must hold both the kernel and the collectives."""
    cfg = dataclasses.replace(CFG_1B, param_dtype=jnp.bfloat16,
                              attn_impl="flash", loss_chunk=256, n_layers=2)
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), topo.devices)
    opt = ts.default_optimizer(total_steps=100)
    plan = compile_plan(cfg, mesh)
    p_sh, o_sh = plan.state_shardings(opt)
    p_abs = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    o_abs = jax.eval_shape(opt.init, p_abs)
    as_sharded = lambda tree, sh: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh)
    k = 2
    batch = {"tokens": jax.ShapeDtypeStruct(
        (k, 2, 2049), jnp.int32, sharding=plan.batch_sharding(3, False, True))}
    multi = ts.make_multi_step(cfg, opt, k, mesh=mesh, plan=plan)
    with mesh_scope(mesh):
        compiled = multi._jit.lower(as_sharded(p_abs, p_sh),
                                    as_sharded(o_abs, o_sh), batch).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text and "all-reduce" in text


def test_libtpu_accepts_the_perf_flags():
    """libtpu aborts the process on a flag it does not know, and every
    worker passes ``TPU_PERF_FLAGS``. Its flags are parsed when the library
    comes up, which describing a topology is enough for."""
    from ray_tpu.parallel.xla_flags import TPU_PERF_FLAGS

    code = ("from jax.experimental import topologies as t; "
            "t.get_topology_desc(platform='tpu', topology_name='v5e:2x2'); "
            "print('LIBTPU-UP')")

    def up(flags):
        # (this process may hold libtpu's one-process-per-host lock)
        env = dict(os.environ, LIBTPU_INIT_ARGS=" ".join(flags),
                   TPU_LOG_DIR="disabled", ALLOW_MULTIPLE_LIBTPU_LOAD="1")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        return "LIBTPU-UP" in proc.stdout, proc.stderr[-2000:]

    ok, err = up(TPU_PERF_FLAGS + ("--xla_tpu_no_such_flag=true",))
    if ok or "Unknown command line flag" not in err:
        pytest.skip(f"libtpu does not come up and parse its flags here: {err}")
    ok, err = up(TPU_PERF_FLAGS)
    assert ok, f"libtpu refused TPU_PERF_FLAGS: {err}"
