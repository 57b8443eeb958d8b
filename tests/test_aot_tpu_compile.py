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

import collections
import dataclasses
import math
import os
import re
import shutil
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import generate, hybrid, llama, moe, sambay, serving
from ray_tpu.ops.pallas import flash
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.context import mesh_scope
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.plan import compile_plan
from ray_tpu.util import hlo_copies

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


def _as_sharded(tree, shardings):
    return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), tree, shardings)


@pytest.mark.parametrize("b,s,h,hkv,d", [
    (8, 2048, 16, 16, 64),    # the 410m widths
    (4, 2048, 32, 4, 64),     # "1b": grouped-query, 32 heads over 4
    (2, 2048, 32, 32, 128),   # head_dim 128
    (1, 4096, 32, 8, 128),    # the benchmark's train cells, a chip
])
def test_flash_fwd_bwd_compiles_for_v5e(topo, b, s, h, hkv, d):
    from benchmark.kernels.flash import call_kind

    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    # forward, dq, dk/dv: three calls, each with the results the benchmark's
    # matcher tells them apart by and counts their work from
    calls = [call_kind(line.strip().lstrip("%"))
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sorted(calls) == [(kind, b * h, s, d, 2)
                             for kind in ("dkv", "dq", "fwd")], calls


@pytest.mark.parametrize("window", [4096, None])
def test_banded_flash_compiles_for_v5e_at_trinitys_shape(topo, window):
    """The Trinity cell's attention (48 heads over 8 of 128, s 8192), banded
    and full: Mosaic takes the band's clamped index maps and masks, and each
    call carries the name ``benchmark/kernels/flash_band.py`` costs it by."""
    from benchmark.kernels import flash_band

    b, s, h, hkv, d = 1, 8192, 48, 8, 128
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return flash.flash_attention(q, k, v, causal=True, window=window
                                     ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    calls = [flash_band.call_shape(line.strip())
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sorted(calls) == [(kind, b * h, s, s, d, True, window or 0, 2)
                             for kind in ("dkv", "dq", "fwd")], calls
    for kind in flash.KINDS:
        p = flash.plan(s, s, d, 2, True, kind, window=window)
        assert p.vmem_bytes <= p.vmem_limit_bytes <= 64 << 20, p
        assert (p.block_q, p.block_k, p.live_steps) == (
            1024, 1024, 30 if window else 36), p


_KDA_INSIDES = """if True:
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.pallas import flash, kda_insides
    flash._needs_interpret = lambda: False
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    qkv = jax.ShapeDtypeStruct((1, 32, 8, 64, 128), jnp.bfloat16, sharding=one)
    g = jax.ShapeDtypeStruct((1, 32, 8, 64, 128), jnp.float32, sharding=one)
    beta = jax.ShapeDtypeStruct((1, 32, 8, 64), jnp.float32, sharding=one)
    loss = lambda *a: sum((o.astype(jnp.float32) ** 2).sum()
                          for o in kda_insides.insides(*a, 16))
    jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qkv, qkv, qkv, g, beta).compile()
"""


_EVA_MIX = """if True:
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.pallas import eva_mix, flash
    flash._needs_interpret = lambda: False
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    x = jax.ShapeDtypeStruct((1, 16384, 4096), jnp.bfloat16, sharding=one)
    table = jax.ShapeDtypeStruct((16384, 64), jnp.bfloat16, sharding=one)
    phi = jax.ShapeDtypeStruct((32, 128), jnp.bfloat16, sharding=one)
    loss = lambda *a: sum((o.astype(jnp.float32) ** 2).sum()
                          for o in eva_mix.mix(*a, 2048, 16))
    jax.jit(jax.grad(loss, argnums=(0, 1, 2, 5, 6))).lower(
        x, x, x, table, table, phi, phi).compile()
"""


_HYPER_MIX = """if True:
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops import hyper
    from ray_tpu.ops.pallas import flash
    flash._needs_interpret = lambda: False
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    on = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    n, s, d = 4, 8192, 3584
    half = {"g": on((n * d,), jnp.bfloat16),
            "phi": on((n * d, 24), jnp.bfloat16),
            "b": on((24,), jnp.float32), "alpha": on((3,), jnp.float32)}

    def loss(x, half):
        h, mix = hyper.mix_in(x, half, iters=20, eps=1e-6, clamp=(-30., 30.),
                              norm_eps=1e-6, impl="pallas")
        return (hyper.mix_out(x, h + h, mix).astype(jnp.float32) ** 2).sum()

    jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        on((n, 1, s, d), jnp.bfloat16), half).compile()
"""


def _scheduled_bundles(dump, script, calls, ways=("bwd", "fwd")):
    """{way: the bundles the compiler schedules for the call ``calls`` (a
    pattern with one group, the way: ``ways``, sorted) names}: ``script``
    compiled for a described v5e in a process of its own that starts with
    ``--xla_jf_dump_to`` (libtpu reads the flag once, writes
    ``*<call>*schedule-analysis_final_bundles.txt`` among much else, and
    aborts when it is done)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump}",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    done = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    found = {}
    for name in os.listdir(dump):
        m = re.search(calls + r".*schedule-analysis_final_bundles", name)
        if m:
            with open(os.path.join(dump, name)) as f:
                found[m.group(1)] = int(re.search(
                    r"total scheduled bundles:\s+(\d+)", f.read()).group(1))
    shutil.rmtree(dump, ignore_errors=True)
    assert sorted(found) == list(ways), done.stderr[-2000:]
    return found


@pytest.fixture(scope="module")
def kda_insides_schedule(topo, tmp_path_factory):
    """A segment of the Kimi Linear cell (32 heads x 8 chunks of 64 x 128,
    bf16) through ``ops/pallas/kda_insides.py``."""
    return _scheduled_bundles(
        tmp_path_factory.mktemp("kda_insides_schedule"), _KDA_INSIDES,
        r"kda_insides_(fwd|bwd)_bh32_n8_c64_k128_v128")


@pytest.fixture(scope="module")
def eva_mix_schedule(topo, tmp_path_factory):
    """An EVA layer of the EvaByte cell (b1 x s16384, 32 heads of 128, chunk
    16, bf16) through ``ops/pallas/eva_mix.py``."""
    return _scheduled_bundles(
        tmp_path_factory.mktemp("eva_mix_schedule"), _EVA_MIX,
        r"eva_mix_(fwd|bwd)_s16384_h32_d128_c16")


@pytest.fixture(scope="module")
def hyper_mix_schedule(topo, tmp_path_factory):
    """A half layer of the Xing4 cell (a stream of 4 rows, b1 x s8192 x
    3584, bf16) through both mixes of ``ops/pallas/hyper_mix.py``."""
    return _scheduled_bundles(
        tmp_path_factory.mktemp("hyper_mix_schedule"), _HYPER_MIX,
        r"mhc_(in_fwd|out_fwd|out_bwd|in_bwd)_n4_t8192_d3584",
        ways=("in_bwd", "in_fwd", "out_bwd", "out_fwd"))


def test_the_four_hyper_mix_calls_compile_and_keep_pace_with_hbm(
        hyper_mix_schedule, capsys):
    """Mosaic takes the four calls at the cell's shape and dtype (a tile of
    256 tokens' four rows, the coefficients turned once a tile, ``phi``'s
    product with ``d`` contracted of both operands, twenty iterations forward
    and back on four slabs), and the schedule leaves each bound by HBM: a
    grid step moves 9.2 MB (``mix_in`` forward), 16.5 (``mix_out`` forward),
    25.7 (``mix_out`` backward) and 23.9 MB (``mix_in`` backward), 16,800 to
    47,000 cycles at 819 GB/s and 1.5 GHz. A call's bundles as counted here
    are its text's, each loop's body once: eight trips of 32 tokens a grid
    step. ``mix_out`` 2,659 forward (2,594 a trip: 20,800 a grid step) and
    4,452 backward (4,326 a trip: 34,700); ``mix_in`` 5,065 forward (two
    loops of 377 and 421 a trip and 4,178 between them, the product and the
    iterations: 10,600) and 6,331 backward (387 and 1,230 a trip, 4,500
    between: 17,500) (PR 57)."""
    with capsys.disabled():
        print("\nhyper_mix at 4 x 8192 x 3584: a call's bundles, "
              + ", ".join(f"{k} {v}" for k, v in sorted(
                  hyper_mix_schedule.items())))
    most = {"out_fwd": 3000, "out_bwd": 5000, "in_fwd": 5700, "in_bwd": 7100}
    assert all(hyper_mix_schedule[k] <= v for k, v in most.items()), \
        hyper_mix_schedule


def test_the_eva_mix_pair_compiles_and_keeps_pace_with_hbm(eva_mix_schedule,
                                                           capsys):
    """Mosaic takes both calls at the cell's shape and dtype (a head's
    lanes at an offset the loop computes, the chunks as [16, 16, 128], the
    logits as an NT product), and the schedule leaves them bound by HBM: a
    grid step moves 256 rows of three streams in and out, 12.6 MB forward
    and 17 MB backward, ~15,000 and ~20,000 cycles at the chip's ~810 GB/s;
    its instructions are the call's bundles less a prologue, 32 trips of
    the head loop's body. Forward 772 and backward 1,204 (PR 55; 1,454
    backward with a chunk a trip of a Python loop, whose sums the scheduler
    left one behind the other, 3.2 ms a call on the chip): the chip took
    1.29 and 1.70 ms a call, ~630 GB/s."""
    with capsys.disabled():
        print(f"\neva_mix at b1 x s16384 x 32 x 128: bundles a grid step's "
              f"prologue and one head, forward {eva_mix_schedule['fwd']}, "
              f"backward {eva_mix_schedule['bwd']}")
    assert eva_mix_schedule["fwd"] <= 900 and eva_mix_schedule["bwd"] <= 1300


@pytest.mark.parametrize("way,most", [("fwd", 800), ("bwd", 1300)])
def test_the_kda_insides_schedule_is_what_the_order_of_writing_buys(
        kda_insides_schedule, way, most):
    """Mosaic takes both calls at the cell's shape and dtype, and the
    schedule holds what ``kda_insides``' order of writing was chosen for
    (its docstring; PERF.md section 6, PR 53): bundles a chunk and head, the
    whole call's over the chunks a trip of its loop. Forward 765 with the
    next chunk's products written between this chunk's sums, two chunks a
    trip (1,113 with one chunk a trip and nothing between). Backward 1,235
    with the inverse handed over and four chunks a trip (1,780 with the
    inverse rebuilt; what writing the next chunk's products between the sums
    buys there shows on the chip, 10.5 -> 8.6 ms a call, and not in this
    count). A libtpu that schedules the same text worse, or an edit that
    undoes the order, fails here before any chip is asked."""
    from ray_tpu.ops.pallas import kda_insides

    together = kda_insides._together(8, {"fwd": kda_insides._TOGETHER,
                                         "bwd": kda_insides._TOGETHER_BACK}[way])
    assert kda_insides_schedule[way] / together <= most


def test_the_kda_insides_kernels_compile_for_v5e_in_float32(topo):
    """The same segment with float32 inputs (the products between sub-blocks
    and ``W``, ``U`` then take float32 operands): Mosaic takes the pair that
    holds everything of a chunk but the state (the running sum as three
    bfloat16 products, the substitution by columns, the merge's products at
    ``highest`` on operands a lane slice cuts, the inverse handed from the
    forward to the backward), each call under the name a trace shows, which
    ``benchmark/kernels/kda_scan.py`` does not take for the whole
    recurrence."""
    from ray_tpu.ops.pallas import kda_insides

    one = SingleDeviceSharding(topo.devices[0])
    qkv = jax.ShapeDtypeStruct((1, 32, 8, 64, 128), jnp.float32, sharding=one)
    beta = jax.ShapeDtypeStruct((1, 32, 8, 64), jnp.float32, sharding=one)

    def loss(*a):
        return sum((o.astype(jnp.float32) ** 2).sum()
                   for o in kda_insides.insides(*a, 16))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qkv, qkv, qkv, qkv, beta).compile()
    calls = [re.search(r"kda_insides_(fwd|bwd)_bh32_n8_c64_k128_v128",
                       line).group(1)
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sorted(calls) == ["bwd", "fwd"], calls
    from benchmark.kernels import kda_scan
    assert not kda_scan._CALL.match("kda_insides_fwd_bh32_n8_c64_k128_v128")


def test_flash_plan_at_the_train_cells_shape():
    """What ``plan`` chooses where the benchmark trains (bh 32, s 4096,
    d 128, bf16, causal): tiles of at least 512 a side, at most 0.65 of the
    grid doing work (36 of 64 at 512², 10 of 16 at 1024²; 1.0 at the 128²
    of before, masked blocks and all), inside the VMEM limit the call sets,
    which the compile above was held to."""
    for kind in flash.KINDS:
        p = flash.plan(4096, 4096, 128, 2, True, kind)
        assert min(p.block_q, p.block_k) >= 512, p
        assert p.live_steps / p.grid_steps <= 0.65, p
        assert p.vmem_bytes <= p.vmem_limit_bytes <= 64 << 20, p


def test_flash_with_traced_offset_compiles_for_v5e(topo):
    """``parallel/context.py``'s ring attention slides the causal mask with
    a traced ``q_offset`` (an SMEM scalar)."""
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 1024, 8, 64), jnp.bfloat16, sharding=one)
    off = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    compiled = jax.jit(lambda q, k, v, o: flash.flash_attention_with_lse(
        q, k, v, causal=True, q_offset=o)).lower(q, q, q, off).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _engine_args(topo, slots, max_len, cfg=CFG_1B, init=llama.init_params):
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(lambda: init(jax.random.key(0), cfg)))
    cache = jax.ShapeDtypeStruct(
        (cfg.n_layers, slots, max_len, cfg.n_kv_heads, cfg.head_dim),
        cfg.compute_dtype, sharding=one)
    return params, cache, lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one)


def _cache_bytes(cache):
    return 2 * cache.size * cache.dtype.itemsize  # K and V


def _traffic(compiled, cache, bucket):
    """What eight fused steps of ``bucket`` rows do to a cache of 2048
    positions, the branches of the bounded read known to the counter."""
    return hlo_copies.cache_traffic(compiled, cache, rows=bucket, steps=8,
                                    bounds=generate.kv_read_bounds(2048))


def test_engine_prefill_compiles_for_v5e_at_1b(topo):
    """The prefill takes the slot cache donated: one row is written, the
    cache is neither copied nor held twice."""
    params, cache, i32 = _engine_args(topo, 8, 2048)
    compiled = serving._compiled_slot_prefill(CFG_1B, 1024, 8, 2048).lower(
        params, cache, cache, i32(1, 1024), i32()).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes) < 16 * 2 ** 30
    assert mem.alias_size_in_bytes >= _cache_bytes(cache)


# the widths the benchmark's serve cells run (Mistral-7B: 8 KV heads of 128),
# depth cut for the test's time, and the "1b" preset (4 KV heads of 64)
CFG_7B_WIDE = llama.LlamaConfig(
    vocab_size=32768, d_model=4096, n_layers=4, n_heads=32, n_kv_heads=8,
    d_ff=14336, max_seq_len=2048, rope_theta=1e6, tie_embeddings=False,
    param_dtype=jnp.bfloat16)


@pytest.mark.parametrize("widths,slots,bucket", [
    ("7b", 16, 16), ("7b", 16, 1), ("1b", 8, 8), ("1b", 8, 1)])
def test_engine_decode_steps_the_cache_in_place_on_v5e(topo, widths, slots,
                                                       bucket):
    """Eight fused decode steps over the full bucket (the engine's widest
    launch) and over a lone row: the TPU compiler's copies are the ones that
    cost, so this is where the mechanism is guarded. Weights in bf16, as
    they are served (the casts of float32 weights are temporaries of their
    own). At both widths the cache is aliased from input to output and the
    program holds neither a copy of the gathered cache nor a per-layer
    stack-back. At the served widths, whose (8, 128) rows are the
    compiler's own tile, it also holds no other: temporaries are smaller
    than one cache, and per step exactly the layer's rows are staged for
    the attention product (on-chip, by the chip run of PR 24). At the
    "1b" widths the compiler re-tiles the cache round the launch (a copy
    in and out a launch, more than one cache of temporaries): known, not
    held to the bound."""
    cfg = (CFG_7B_WIDE if widths == "7b"
           else dataclasses.replace(CFG_1B, param_dtype=jnp.bfloat16))
    params, cache, i32 = _engine_args(topo, slots, 2048, cfg)
    compiled = serving._compiled_bucket_scan(
        cfg, bucket, slots, 2048, 8).lower(
        params, cache, cache, i32(bucket), i32(bucket), i32()).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes) < 16 * 2 ** 30
    assert mem.alias_size_in_bytes >= _cache_bytes(cache)
    traffic = _traffic(compiled, cache, bucket)
    assert traffic["cache_donated"]
    if widths == "7b":
        assert mem.temp_size_in_bytes < _cache_bytes(cache)
        assert traffic["cache_copy_bytes_per_step"] \
            <= traffic["cache_bytes"] * bucket // slots, traffic
    # by name: no copy of the cache as the gather laid it out ([rows, L,
    # ...]), and no dynamic-update-slice whose update is a layer's rows
    # (the stack-back of a cache that is the layer scan's xs and ys)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    for line in compiled.as_text().splitlines():
        assert f"bf16[{bucket},{cfg.n_layers},2048,{hkv},{hd}]" not in line, \
            line
        if " dynamic-update-slice(" in line:
            assert f"bf16[{bucket},2048,{hkv},{hd}]" not in line \
                and f"bf16[{bucket},1,2048,{hkv},{hd}]" not in line \
                and f"bf16[1,{bucket},2048,{hkv},{hd}]" not in line, line


# OLMoE-1B-7B at its published widths and the depth its cell serves
# (benchmark/configs/olmoe-1b-7b-0125.json): 64 experts of width 1024, top-8
# un-renormalised, QK-norm, 16 KV heads
CFG_OLMOE = moe.MoEConfig(
    vocab_size=50304, d_model=2048, n_layers=13, n_heads=16, n_kv_heads=16,
    d_ff=1024, max_seq_len=2048, rope_theta=1e4, tie_embeddings=False,
    param_dtype=jnp.bfloat16, n_experts=64, top_k=8, norm_topk_prob=False,
    qk_norm=True)


@pytest.mark.parametrize("program", ["prefill-1536", "decode-16x8",
                                     "decode-1x8"])
def test_olmoe_engine_programs_compile_for_v5e(topo, program):
    """The served expert path at OLMoE's widths: the chat grid's longest
    prefill (1536 tokens, 12,288 assignments over 64 experts) and the
    in-place decode programs fit the chip. The one-hot form needed 2.4 GB
    each for ``dispatch`` and ``combine`` [G, E, C] and 3.2 GB of expert
    rows a layer; sorted by expert and multiplied in tiles, a prefill's
    temporaries stay under 1 GiB. The cache is still donated and crosses HBM once a step."""
    params, cache, i32 = _engine_args(topo, 16, 2048, CFG_OLMOE,
                                      moe.init_params)
    if program == "prefill-1536":
        compiled = serving._compiled_slot_prefill(
            CFG_OLMOE, 1536, 16, 2048).lower(
            params, cache, cache, i32(1, 1536), i32()).compile()
    else:
        bucket = 16 if program == "decode-16x8" else 1
        compiled = serving._compiled_bucket_scan(
            CFG_OLMOE, bucket, 16, 2048, 8).lower(
            params, cache, cache, i32(bucket), i32(bucket), i32()).compile()
        traffic = _traffic(compiled, cache, bucket)
        assert traffic["cache_donated"]
        assert traffic["cache_copy_bytes_per_step"] \
            <= 1.01 * traffic["cache_bytes"] * bucket // 16, traffic
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes
            - mem.alias_size_in_bytes) < 15.75 * 2 ** 30
    assert mem.alias_size_in_bytes >= _cache_bytes(cache)
    assert mem.temp_size_in_bytes < 2 ** 30
    # the experts' products are the two grouped-matmul kernels, which read
    # the stacked weights where they lie: no layer's experts are sliced out
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "bf16[64,2048,1024]" not in text and "bf16[64,1024,2048]" not in text


# granite-4.0-h-micro at its published widths (benchmark/configs/
# granite-4.0-h-micro.json), two of its four periods of ten layers for the
# test's time (the period loop then runs twice), 64 slots as its cell has
CFG_GRANITE = hybrid.HybridConfig(
    vocab_size=100352, d_model=2048, n_layers=20, n_heads=32, n_kv_heads=8,
    d_ff=8192, max_seq_len=2048, norm_eps=1e-5, tie_embeddings=True,
    param_dtype=jnp.bfloat16, use_rope=False, attn_scale=0.015625,
    embedding_multiplier=12.0, residual_multiplier=0.22, logits_scaling=8.0,
    layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 2)


def _hybrid_args(topo, slots):
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: hybrid.init_params(jax.random.key(0), CFG_GRANITE)))
    tree = _on(one, jax.eval_shape(
        lambda: generate.init_cache(CFG_GRANITE, slots, 2048)))
    buffers = [tree[name] for name in generate.cache_names(CFG_GRANITE)]
    return params, tree, buffers, lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one)


def _tree_bytes(tree):
    return sum(x.size * x.dtype.itemsize for x in tree.values())


def _no_weight_stack_is_copied(compiled):
    """No program transposes a kind's stacked weights before its layer loop:
    the chip keeps an array whose last size is no multiple of 128 lanes with
    another size minor, and ``in_proj`` stored [in, 8512] was copied whole
    by every prefill and every decode launch (3.84 ms each on the chip:
    PERF.md, PR 31); stored [out, in] it is read where it lies."""
    for line in compiled.as_text().splitlines():
        if " copy(" in line and "%params__layers__" in line:
            # an attention layer's k and v projections ([2, 2048, 512], 4 MB
            # the pair) are copied; nothing a layer's size or more is
            sizes = line.split(" = ")[1].split("[")[1].split("]")[0]
            assert math.prod(map(int, sizes.split(","))) < 2 ** 23, line[:300]


@pytest.mark.parametrize("bucket", [64, 1])
def test_hybrid_decode_steps_the_whole_slot_tree_in_place_on_v5e(topo, bucket):
    """The hybrid's decode program: every buffer of the slot tree (K, V, the
    float32 recurrent state, the convolution tail) is aliased from input to
    output, and the state moves exactly 2.0 x its rows' bytes a step, once
    read and once written where it lies: the update is the Pallas call
    ``ssm_update_r<rows>_...`` on the stacked state (written in XLA it was
    two fusions that each read the rows' state, 3.0 x: PERF.md, PR 31), and
    nothing state-shaped is sliced out or stacked back. K and V at head 64
    are re-tiled round the attention product, as at the "1b" widths above:
    known, held to 2.5 x the cache for the full bucket and not to 1.0."""
    params, tree, buffers, i32 = _hybrid_args(topo, 64)
    compiled = serving._compiled_bucket_scan(
        CFG_GRANITE, bucket, 64, 2048, 8).lower(
        params, *buffers, i32(bucket), i32(bucket), i32()).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _tree_bytes(tree)
    traffic = _traffic(compiled, tree, bucket)
    assert traffic["cache_donated"] and traffic["state_donated"]
    rows_state = traffic["state_bytes"] * bucket // 64
    assert traffic["state_bytes"] == 18 * 64 * 64 * 64 * 128 * 4
    assert traffic["state_copy_bytes_per_step"] == 2 * rows_state, traffic
    # the lone row's launch copies K and V in once for its 8 steps (0.28 x
    # the cache a step), the "1b" widths' re-tiling again
    assert traffic["cache_copy_bytes_per_step"] \
        <= (2.5 if bucket == 64 else 0.3) * traffic["cache_bytes"], traffic
    text = compiled.as_text()
    assert f"ssm_update_r{bucket}_h64_p64_n128" in text
    _no_weight_stack_is_copied(compiled)
    for line in text.splitlines():  # no layer's rows of the state on their own
        if " copy(" in line or " dynamic-update-slice(" in line:
            assert f"f32[{bucket},64,64,128]" not in line \
                and f"f32[1,{bucket},64,64,128]" not in line, line


@pytest.mark.parametrize("length", [128, 384])
def test_hybrid_prefill_compiles_for_v5e(topo, length):
    """The cell's two prompt lengths (half a chunk of the scan; a chunk and
    a half): the whole tree donated, one row's bytes written, and the
    chunked scan's temporaries far from the chip's memory."""
    params, tree, buffers, i32 = _hybrid_args(topo, 64)
    compiled = serving._compiled_slot_prefill(
        CFG_GRANITE, length, 64, 2048).lower(
        params, *buffers, i32(1, length), i32()).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _tree_bytes(tree)
    assert mem.temp_size_in_bytes < 2 ** 30
    _no_weight_stack_is_copied(compiled)


# Phi-4-mini-flash-reasoning at its published widths and depth, 64 slots as
# its cell has (benchmark/configs/phi-4-mini-flash-reasoning.json)
CFG_PHI4FLASH = sambay.SambaYConfig(
    vocab_size=200064, d_model=2560, n_layers=32, n_heads=40, n_kv_heads=20,
    d_ff=10240, max_seq_len=2048, norm_eps=1e-5, tie_embeddings=True,
    param_dtype=jnp.bfloat16, use_rope=False, attn_scale=0.125,
    layer_types=sambay.layer_types_for(32))


def _sambay_args(topo, slots=64):
    one = SingleDeviceSharding(topo.devices[0])
    params = _on(one, jax.eval_shape(
        lambda: sambay.init_params(jax.random.key(0), CFG_PHI4FLASH)))
    tree = _on(one, jax.eval_shape(
        lambda: generate.init_cache(CFG_PHI4FLASH, slots, 2048)))
    buffers = [tree[name] for name in generate.cache_names(CFG_PHI4FLASH)]
    return params, tree, buffers, lambda *shape: jax.ShapeDtypeStruct(
        shape, jnp.int32, sharding=one)


def _largest_copy(compiled, but=()):
    """Elements of the largest array any ``copy`` of the program makes,
    arrays of the shapes ``but`` left out."""
    return max([math.prod(dims) for line in compiled.as_text().splitlines()
                if " copy(" in line
                for _, dims in hlo_copies._arrays(
                    line.split(" = ", 1)[1].split(" copy(")[0])
                if dims not in but] or [0])


@pytest.mark.parametrize("bucket", [64, 1])
def test_sambay_decode_steps_three_cache_shapes_in_place_on_v5e(topo, bucket):
    """The cell's decode program, all 32 layers: every buffer of the slot
    tree (the shared K/V, the rings, the float32 state, the tails) is aliased
    from input to output; the state moves exactly 2.0 x its rows' bytes a step
    (``s6_update_r<rows>_...`` on the stacked state); of keys and values
    nothing is written but a 16-position tile a row, buffer and layer that
    writes (``kv_write_r<rows>_...``: 8 window layers and the full one), and
    nothing cache-sized or weight-stack-sized is copied at all; the read is
    the rings whole and the shared buffer below the bound, once for each of
    its eight readers; and the program has 9 MB of temporaries of its own
    beside 9.9 GB of weights and slot tree (12.2 GB at 128 slots: both fit)."""
    params, tree, buffers, i32 = _sambay_args(topo)
    compiled = serving._compiled_bucket_scan(
        CFG_PHI4FLASH, bucket, 64, 2048, 8).lower(
        params, *buffers, i32(bucket), i32(bucket), i32()).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _tree_bytes(tree)
    assert mem.temp_size_in_bytes < 2 ** 27
    traffic = hlo_copies.cache_traffic(
        compiled, tree, rows=bucket, steps=8,
        bounds=generate.kv_read_bounds(2048), length_axis=3)
    assert traffic["cache_donated"] and traffic["state_donated"]
    assert traffic["state_bytes"] == 9 * 64 * 16 * 5120 * 4
    rows_state = traffic["state_bytes"] * bucket // 64
    if bucket == 64:
        assert traffic["state_copy_bytes_per_step"] == 2 * rows_state, traffic
    else:  # a lone row's ``A`` [16, 5120] is as large as its state and counts
        assert traffic["state_copy_bytes_per_step"] <= 4.2 * rows_state, traffic
    tile = 10 * 16 * 128 * 2
    assert traffic["cache_copy_bytes_per_step"] == 9 * 2 * 2 * bucket * tile
    rings, shared = 8 * 512 * 5120, 5120  # K and V: a row; a row and position
    # (the counter goes by slices: where the launch is every row of the one
    # layer there is, the full layer's own read is of the buffer as it
    # stands, and seven of the eight readers are counted)
    readers = 8 if bucket == 1 else 7
    assert traffic["cache_read_bytes_per_step"] \
        == bucket * (rings + readers * 2048 * shared), traffic
    assert traffic["cache_read_bytes_per_step_least"] \
        <= bucket * (rings + readers * 256 * shared), traffic
    assert traffic["window_bytes"] == 64 * rings
    text = compiled.as_text()
    assert f"s6_update_r{bucket}_n16_c5120" in text
    assert f"kv_write_r{bucket}_h10_t16_d128" in text
    # (the lone row's launch re-lays the convolution tails, 35 MB, on its
    # way in and out; no weight stack, nothing of K, V or the state)
    assert _largest_copy(compiled, (tree["conv"].shape,)) < 2 ** 23


@pytest.mark.parametrize("length", [128, 384])
def test_sambay_prefill_compiles_for_v5e(topo, length):
    """The cell's two prompt lengths: the whole tree donated, one row's bytes
    written, temporaries far from the chip's memory, no weight stack copied."""
    params, tree, buffers, i32 = _sambay_args(topo)
    compiled = serving._compiled_slot_prefill(
        CFG_PHI4FLASH, length, 64, 2048).lower(
        params, *buffers, i32(1, length), i32()).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _tree_bytes(tree)
    assert mem.temp_size_in_bytes < 2 ** 29
    assert _largest_copy(compiled) < 2 ** 23


@pytest.mark.parametrize("family,bucket", [
    ("dense", 16), ("dense", 1), ("olmoe", 16), ("olmoe", 1),
    ("hybrid", 64), ("hybrid", 1)])
def test_decode_reads_below_the_bound_on_v5e(topo, family, bucket):
    """The bounded read as the chip's compiler leaves it, for the three
    families' decode programs: one conditional a layer whose branches slice
    256, 512 ... 2048 positions of the launch's rows out of the carried
    cache. At the smallest bound a step reads an eighth of what it read
    when every allocated position was read, at the largest exactly that
    (the rows' share of the cache, once); the cache is still donated and
    nothing larger than one layer's rows is materialised (at head 64 the
    rows are re-tiled round the products, as before: 1.25 x at the full
    bound where the whole-row program had 2.25); the state moves 2.0 x its
    rows; no weight stack is copied that the whole-row program did not copy
    (the lone row's ``wo`` would be, were it not held: PERF.md, PR 32)."""
    if family == "hybrid":
        cfg, slots = CFG_GRANITE, 64
        params, cache, buffers, i32 = _hybrid_args(topo, slots)
    else:
        cfg, slots = (CFG_7B_WIDE, 16) if family == "dense" else (CFG_OLMOE, 16)
        params, cache, i32 = _engine_args(
            topo, slots, 2048, cfg,
            llama.init_params if family == "dense" else moe.init_params)
        buffers = [cache, cache]
    compiled = serving._compiled_bucket_scan(
        cfg, bucket, slots, 2048, 8).lower(
        params, *buffers, i32(bucket), i32(bucket), i32()).compile()
    traffic = _traffic(compiled, cache, bucket)
    share = traffic["cache_bytes"] * bucket // slots
    assert traffic["cache_donated"]
    assert traffic["cache_read_bytes_per_step"] == share, traffic
    assert traffic["cache_read_bytes_per_step_least"] \
        == share * generate.KV_CHUNK // 2048, traffic
    text = compiled.as_text()
    assert text.count(" conditional(") == 1  # one attention block, in loops
    shape = cache["k"].shape if family == "hybrid" else cache.shape
    whole = ",".join(map(str, shape))
    for line in text.splitlines():  # no copy of the cache round a branch
        if " copy(" in line and f"bf16[{whole}]" in line:
            # (at head 64 both buffers are converted on a launch's entry
            # and back on its exit, as before: outside every loop)
            assert family == "hybrid" and "while/body" not in line, line[:300]
    if family == "dense":
        assert traffic["cache_copy_bytes_per_step"] <= share, traffic
    elif family == "olmoe":  # MHA: the slices fuse into their products
        assert traffic["cache_copy_bytes_per_step"] == 0, traffic
    else:
        assert traffic["cache_copy_bytes_per_step"] <= (
            1.3 if bucket == 64 else 0.3) * traffic["cache_bytes"], traffic
        assert traffic["state_copy_bytes_per_step"] \
            == 2 * traffic["state_bytes"] * bucket // slots, traffic
        _no_weight_stack_is_copied(compiled)
    if bucket == 1:  # the lone row's program copies no weight stack at all
        assert not [line for line in text.splitlines() if " copy(" in line
                    and "%params__layers__" in line
                    and "router" not in line], "a weight stack is copied"


def _compile_fused_step(fam, cfg, mesh, k, batch, seq):
    """The fused-K train step of ``cfg`` compiled for ``mesh``'s described
    chips: (plan, parameter shardings, compiled). Module fixtures are set up
    before the function-scoped ``compiled_kernel``, so the Mosaic kernel is
    asked for here too."""
    opt = ts.default_optimizer(total_steps=100)
    plan = compile_plan(cfg, mesh)
    p_sh, o_sh = plan.state_shardings(opt)
    p_abs = jax.eval_shape(lambda: fam.init_params(jax.random.key(0), cfg))
    o_abs = jax.eval_shape(opt.init, p_abs)
    tokens = {"tokens": jax.ShapeDtypeStruct(
        (k, batch, seq + 1), jnp.int32,
        sharding=plan.batch_sharding(3, False, True))}
    multi = ts.make_multi_step(cfg, opt, k, mesh=mesh, plan=plan)
    with pytest.MonkeyPatch.context() as mp, mesh_scope(mesh):
        mp.setattr(flash, "_needs_interpret", lambda: False)
        return plan, p_sh, multi._jit.lower(
            _as_sharded(p_abs, p_sh), _as_sharded(o_abs, o_sh),
            tokens).compile()


@pytest.fixture(scope="module")
def flash_step(topo):
    """The fused-K step over fsdp x tp on four described chips, "1b" widths,
    depth cut to two layers for the test's time: (cfg, K, compiled)."""
    cfg = dataclasses.replace(CFG_1B, param_dtype=jnp.bfloat16,
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


def _head_collectives(compiled, dims):
    """The collectives of a compiled step whose result holds the loss's
    head at ``dims``, one per channel: where the TPU compiler makes a
    collective asynchronous, its start, continuation and done fusions each
    spell the instruction out under the one ``channel_id``, and
    ``hlo_copies.collectives`` lists all three."""
    text = compiled.as_text()
    by_channel = {}
    for c in hlo_copies.collectives(compiled):
        if any(d == dims for _, d in c["arrays"]):
            channel = re.search(
                rf"%{re.escape(c['name'])} = .*?channel_id=(\d+)", text)
            by_channel.setdefault(channel.group(1) if channel else c["name"],
                                  c)
    return list(by_channel.values())


@pytest.mark.parametrize("step", ["mixtral-fsdp4", "1b-fsdp2-tp2"])
def test_the_loss_gathers_its_head_once_a_step(step, request, capsys):
    """``chunked_ce``'s loop closes over a head whole along d
    (``llama.head_for_loss_loop``): the head is gathered once a step before
    the loop and its gradient summed over the chips once after it, V left
    on ``tp``. At most 3 collectives a step hold the head and none runs per
    chunk. Fails on the parent of the change that brought it: there the
    Mixtral step gathers ``[4096, 32000]`` 16 times forward and 16 times in
    the rematted backward a step (64 runs a launch of 2, and 32 more of
    the gradient's ``[1024, 32000]`` reduce-scatter); the ``fsdp 2 x tp 2``
    one gathers ``[2048, 16000]`` 8 + 8 times a step (32 a launch)."""
    if step == "mixtral-fsdp4":
        k, _, seq, compiled = request.getfixturevalue("mixtral_step")
        cfg, tp = CFG_MIXTRAL, 1
    else:
        cfg, k, compiled = request.getfixturevalue("flash_step")
        seq, tp = 2048, 2
    chunks = seq // cfg.loss_chunk
    found = _head_collectives(compiled, (cfg.d_model, cfg.vocab_size // tp))
    with capsys.disabled():
        print(f"\n{step}: collectives that hold the head, a launch of {k}:")
        for c in found:
            print(f"  {c['kind']} {c['arrays']} x{c['runs']} {c['op_name']}")
    assert found and {c["kind"] for c in found} >= {"all-gather"}, found
    assert sum(c["runs"] for c in found) <= 3 * k, found
    for c in found:
        assert c["runs"] % (chunks * k), c
    # nor does anything else as wide as the vocabulary cross chips per chunk
    # (the parent's gradient, reduce-scattered as [d / fsdp, V], and the
    # chunk's [b, 256, V] logits' cotangent, gathered for it)
    # nor once a group: the loop's chunks are unrolled inside a group, so a
    # collective of theirs would run ``groups * k`` times (the ``fsdp 4``
    # step did, 32 gathers of a chunk's cotangent over the batch and the
    # head's once a group, until the head was placed inside the loop's body)
    for c in hlo_copies.collectives(compiled):
        for _, dims in c["arrays"]:
            if len(dims) > 1 and dims[-1] == cfg.vocab_size // tp:
                assert c["runs"] % (chunks * k) and c["runs"] <= k, c


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


# step: (its fixture, kinds of attention layer, and the temporaries and peak
# in bytes of the same compile at commit 7246729, whose remat blocks kept the
# products' results alone)
NO_SECOND_FORWARD = {
    "1b-fsdp2-tp2": ("flash_step", 1, 449518080, 657651712),
    "mixtral-fsdp4": ("mixtral_step", 1, 2467869184, 4975883776),
    "trinity-window-full": ("trinity_step", 2, 3664176128, 8189170688)}


@pytest.mark.parametrize("step", sorted(NO_SECOND_FORWARD))
def test_the_backward_runs_no_second_forward(step, request, capsys):
    """A layer's remat block keeps the flash forward's output and
    log-sum-exp (``llama.remat_block``), so the compiled train step holds as
    many ``flash_fwd`` calls as ``flash_dq`` calls, through the dense scan
    under ``shard_map`` on four chips, Mixtral's scan and the patterned
    walk's banded and full layers alike. Fails on the parent, whose backward
    ran the forward kernel again for them (2 : 1). What the kept arrays
    cost is printed beside the parent's."""
    fixture, layer_kinds, temp, peak = NO_SECOND_FORWARD[step]
    compiled = request.getfixturevalue(fixture)[-1]
    calls = [re.search(r"flash_(fwd|dq|dkv)", line).group(1)
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert sorted(calls) == sorted(flash.KINDS * layer_kinds), calls
    mem = compiled.memory_analysis()
    with capsys.disabled():
        print(f"\n{step}: temporaries {mem.temp_size_in_bytes / 2**20:.1f} "
              f"MiB (parent {temp / 2**20:.1f}), peak "
              f"{mem.peak_memory_in_bytes / 2**20:.1f} MiB (parent "
              f"{peak / 2**20:.1f})")


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


# ---- the chunked loss's loop: three vocabulary-wide products a chunk ----------

def _loss_loop(compiled, scope, cfg, tp=1):
    """What the compiled step runs under ``scope`` of ``llama._looped_ce``'s
    loop, in one launch: (runs of products that read or write
    an array as wide as the vocabulary a chip holds, runs of instructions
    that write a ``[d, V]`` array), each as (name, runs) rows. A product is a
    ``convolution`` inside or outside a fusion; its operands' shapes are its
    computation's parameters'."""
    from benchmark.lib.trace import scope_of

    wide = cfg.vocab_size * max(getattr(cfg, "n_pred_heads", 1), 1) // tp
    module = hlo_copies._Module(compiled.as_text())
    products, writes = [], []
    for times, (name, shape, opcode, operands, line), comp in module.walk(
            fusions=True):
        if scope_of(_op_name(line)) != scope:
            continue
        if opcode == "convolution":
            shapes = [shape] + [i[1] for i in comp if i[0] in operands]
            # (n heads' logits may stand as [chunk, n, V]: a run of
            # dimensions that multiplies to the width)
            if any(math.prod(dims[i:j]) == wide for text in shapes
                   for _, dims in hlo_copies._arrays(text)
                   for i in range(len(dims))
                   for j in range(i + 1, len(dims) + 1)):
                products.append((name, times))
    for times, (name, shape, opcode, _, line), _ in module.walk():
        if (opcode in ("fusion", "convolution", "copy")
                and scope_of(_op_name(line)) == scope
                and any(dims[-2:] == (cfg.d_model, wide)
                        for _, dims in hlo_copies._arrays(shape))):
            writes.append((name, times))
    return products, writes


def _assert_three_products_a_chunk(compiled, scope, k, cfg, seq, tp=1):
    """Under ``scope`` the step's loss runs, a step, two vocabulary-wide
    products a chunk of ``loss_chunk`` positions (the logits, the hidden's
    gradient) and one a GROUP of chunks (the head's gradient), and writes a
    ``[d, V]`` array once a group and not once a chunk. Fails on the
    parent, whose rematted loop ran four a chunk (the logits twice) and
    read and wrote the head's whole cotangent in each (Mistral's shape:
    ``convolution_add_fusion.5``, 64 runs a launch of 4)."""
    chunks = seq // cfg.loss_chunk
    groups = chunks // llama._chunks_a_group(chunks, cfg.loss_chunk)
    products, writes = _loss_loop(compiled, scope, cfg, tp)
    assert sum(runs for _, runs in products) == k * (2 * chunks + groups), (
        scope, products)
    # the groups' sums, and what a step makes of them once: the zero they
    # start from, the incoming cotangent's scale, a cast
    assert k * groups <= sum(runs for _, runs in writes) \
        <= k * (groups + 3), (scope, writes)
    return chunks, groups


# ---- every operation of a compiled train step under a scope of the program's ---

# step: (its fixture, the scopes its operations must be found under, and the
# most instructions that may carry none: the scans' and the walk's own
# stacking and slicing, and fusions or collectives the compiler rooted in an
# instruction of its own; since PR 60 among them the loss's unrolled chunks'
# writes of their cotangent and of their slice of ``dx`` into the group's
# stacks, sixteen in a group of eight, which hold ``loss_head`` only)
STEP_NAMES = {
    "1b-fsdp2-tp2": ("flash_step", {"embed", "attn_full", "mlp", "loss_head",
                                    "optimizer"}, 55, 36),
    "mixtral-fsdp4": ("mixtral_step", {
        "embed", "attn_full", "moe_router", "moe_dispatch", "moe_experts",
        "moe_combine", "loss_head", "optimizer"}, 8, 60),
    "trinity-window-full": ("trinity_step", {
        "embed", "attn_window", "attn_full", "moe_router", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared", "loss_head",
        "optimizer"}, 45, 75),
    "kimi-kda-mla": ("kimi_step", {
        "embed", "attn_kda", "attn_mla", "mlp", "moe_router", "moe_dispatch",
        "moe_experts", "moe_combine", "moe_shared", "loss_head",
        "optimizer"}, 140, 155),
    "evabyte": ("evabyte_step", {"embed", "attn_eva", "mlp", "loss_head",
                                 "optimizer"}, 45, 10)}
# step: (the config whose loss it runs, positions a sequence, ways ``tp``
# splits the vocabulary); the 1b step's config is its fixture's first
LOSS_LOOPS = {"1b-fsdp2-tp2": (None, 2048, 2),
              "mixtral-fsdp4": (CFG_MIXTRAL, 4096, 1),
              "trinity-window-full": (CFG_TRINITY, 8192, 1),
              "kimi-kda-mla": (CFG_KIMI, 16384, 1),
              "evabyte": (CFG_EVABYTE, 16384, 1)}
_TIMED = {"fusion", "convolution", "custom-call", "all-gather", "all-reduce",
          "reduce-scatter", "all-to-all", "collective-permute"}
# a scan's stacking of its per-layer results and slicing of its operands (and
# the patterned walk's picking of a layer out of its period's stack): JAX
# writes them, directly under the loop's body, and no scope can stand there
# (and an index the compiler folded out of such a slice, an ``s32[2]`` that
# carries the enclosing call's name and nothing after it: the widened
# stream's step has fourteen, two microseconds each)
_STACKING = re.compile(r"(/(body|closed_call)/(dynamic_update_slice"
                       r"|dynamic_slice|squeeze|slice|broadcast_in_dim)"
                       r"|/while|/closed_call)$")
_OP_NAME = re.compile(r'op_name="(jit\([^"]*)"')


def _op_name(line):
    """An instruction's ``op_name`` where it is a path of the program's (the
    compiler's own carry none, or a bare word: ``reduce_window_sum``)."""
    found = _OP_NAME.search(line)
    return found.group(1) if found else ""


def _scopes_of_a_step(compiled):
    """(instructions by scope, those of the scans' stacking, those the
    compiler rooted in an instruction of its own, the strays) of a compiled
    step's fusions, products, kernels and collectives outside fused
    computations, each stray and exception as (name, shape, op_name)."""
    from benchmark.lib.trace import scope_of

    module = hlo_copies._Module(compiled.as_text())
    named, stacking, rootless, strays = {}, [], [], []
    seen = set()
    for _, (name, shape, opcode, _, line), _ in module.walk():
        if opcode.replace("-start", "") not in _TIMED or name in seen:
            continue
        seen.add(name)
        if opcode == "custom-call" and "tpu_custom_call" not in line:
            continue  # AllocateBuffer, ConcatBitcast: the compiler's buffers
        op_name = _op_name(line)
        row = (name, shape.split("{")[0][:48], op_name)
        scope = scope_of(op_name)
        if not op_name:
            # the instruction is the compiler's: a fused computation under
            # it is judged by the named operations it holds
            called = re.search(r"calls=%?([\w.\-]+)", line)
            body = module.computations.get(called.group(1), []) if called else []
            inside = {scope_of(_op_name(inst[4])) for inst in body
                      if not _STACKING.search(_op_name(inst[4]))}
            rootless.append(row + (sorted(inside - {"other"}),))
        elif _STACKING.search(op_name):
            stacking.append(row)
        elif scope == "other":
            strays.append(row)
        else:
            named[scope] = named.get(scope, 0) + 1
    return named, stacking, rootless, strays


@pytest.mark.parametrize("step", sorted(STEP_NAMES))
def test_a_train_step_names_all_of_itself(step, request, capsys):
    """Every fusion, product, kernel and collective of the compiled step
    carries an ``op_name`` whose outermost scope, as the benchmark's
    reduction reads it (``benchmark/lib/trace.py:scope_of``), is one of
    ``train_step.STEP_SCOPES``: the old dense stack, the old MoE stack, the
    patterned walk and the EVA block alike, with the embedding, the loss and
    the optimizer's update. Fails on the parent, whose old stacks named
    nothing (``other`` was 89-91% of Mistral's step). What cannot be named
    is printed with its shape and held to a count: the scans' own stacking
    and slicing, and instructions the compiler rooted in one of its own (a
    ``bitcast`` after the last named operation, an expanded ``cumsum``, an
    async collective), whose fused computations hold named operations only.

    The loss is ``llama._looped_ce``'s rule, forward and backward under
    ``loss_head`` with nothing of it astray, and the compiled step holds
    what the rule says (``_assert_three_products_a_chunk``)."""
    fixture, scopes, most_stacking, most_rootless = STEP_NAMES[step]
    made = request.getfixturevalue(fixture)
    named, stacking, rootless, strays = _scopes_of_a_step(made[-1])
    with capsys.disabled():
        print(f"\n{step}: {sum(named.values())} instructions under "
              + ", ".join(f"{k} {v}" for k, v in sorted(named.items()))
              + f"; {len(stacking)} of the scans' stacking, "
              f"{len(rootless)} rooted by the compiler")
        print("  stacking: " + "; ".join(
            f"{name} {shape} {op_name.rsplit('/', 1)[-1]}"
            for name, shape, op_name in stacking))
        print("  rooted by the compiler: " + "; ".join(
            f"{name} {shape} holds {','.join(inside) or '-'}"
            for name, shape, _, inside in rootless))
    assert not strays, strays
    assert set(named) == scopes <= set(ts.STEP_SCOPES), set(named) ^ scopes
    assert all(set(inside) <= set(ts.STEP_SCOPES)
               for *_, inside in rootless), rootless
    assert len(stacking) <= most_stacking and len(rootless) <= most_rootless
    # and of the loss (here, on the compile this case already has: another
    # test's case may run on another worker and compile the step again)
    cfg, seq, tp = LOSS_LOOPS[step]
    cfg, k = (made[0], made[1]) if cfg is None else (cfg, made[0])
    chunks, groups = _assert_three_products_a_chunk(
        made[-1], "loss_head", k, cfg, seq, tp)
    with capsys.disabled():
        print(f"  loss_head: {chunks} chunks in {groups} group(s) a step, "
              f"{2 * chunks + groups} vocabulary-wide products")


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
