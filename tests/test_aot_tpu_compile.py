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
import math
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import generate, hybrid, llama, moe, sambay, serving
from ray_tpu.ops.pallas import flash
from ray_tpu.util import hlo_copies

from _aot import CFG_1B, _on, compiled_kernel, topo  # noqa: F401 (fixtures)


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


_SSM_UPDATE = """if True:
    import jax, jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.pallas import flash
    from ray_tpu.ops.pallas.ssm_update import ssm_update_in_place
    flash._needs_interpret = lambda: False
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    on = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    h, p, n, bf16 = 64, 64, 128, jnp.bfloat16
    jax.jit(ssm_update_in_place, donate_argnums=0).lower(
        on((4, 64, n, h * p)), on((), jnp.int32), on((), jnp.int32),
        on((64, h, p), bf16), on((64, h)), on((h,)), on((64, 1, n), bf16),
        on((64, 1, n), bf16)).compile()
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


@pytest.fixture(scope="module")
def ssm_update_schedule(topo, tmp_path_factory):
    """A layer's update of the Granite cell (64 rows of 64 heads x 64 x 128,
    float32 state) through ``ops/pallas/ssm_update.py``."""
    return _scheduled_bundles(
        tmp_path_factory.mktemp("ssm_update_schedule"), _SSM_UPDATE,
        r"(ssm_update)_r64_h64_p64_n128", ways=("ssm_update",))


def test_the_ssm_update_keeps_pace_with_hbm(ssm_update_schedule, capsys):
    """Mosaic takes the S6 body at Mamba-2's shape (a row's ``[128, 4096]``
    tile a grid step, ``A`` a row ``[1, 4096]``), and the schedule leaves the
    call bound by HBM: a grid step moves a row's 2 MiB each way, 7,650
    cycles at 819 GB/s and 1.5 GHz, and is 1,733 bundles (PR 62: VALU 2,860
    pushes, XLU 248, 965 spills). Kept ``[h, p, n]`` the step was 3,540
    bundles with 5,568 XLU pushes, every head's decay and ``dt x`` a lane
    broadcast and the sum over ``n`` eight cross-lane reductions a head,
    and the chip took 10,600 cycles a row (``ssm_update_roofline`` 71.7
    from PR 31 to PR 61). A libtpu that schedules the body worse, or an edit
    that brings lane work back, fails here before any chip is asked."""
    with capsys.disabled():
        print(f"\nssm_update at 64 rows x [128, 4096]: bundles a row "
              f"{ssm_update_schedule['ssm_update']}")
    assert ssm_update_schedule["ssm_update"] <= 2000


def _mosaic_bodies(lowered):
    """The Mosaic modules of a lowered program's ``tpu_custom_call``s as
    text, without their source locations."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    out = []
    for m in re.finditer(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                         lowered.as_text()):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # ``stable_mosaic``
        with ctx:
            out.append(ir.Module.parse(base64.b64decode(m.group(1)))
                       .operation.get_asm(enable_debug_info=False))
    return out


@pytest.mark.parametrize("rows,digest", [(64, "6386df922b9c18d0"),
                                         (1, "cc7d44d8c10e8c75")])
def test_the_s6_update_lowers_as_the_parents_did(topo, rows, digest):
    """Phi's call is built by the builder Granite's now shares
    (``s6_update.update_in_place``: groups of channels, ``A`` of either
    rank), and at Phi's shape it lowers to the Mosaic module commit 2c5f8c7
    (the parent of PR 62) lowered, to the character, the whole engine's
    launch and the lone row's: the digests are that commit's, taken by this
    function in a checkout of it. (The compiled decode programs of the two
    commits were equal too, outside their tables of source locations.)"""
    import hashlib

    from ray_tpu.ops.pallas.s6_update import s6_update_in_place

    one = SingleDeviceSharding(topo.devices[0])

    def on(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    n, c, bf16 = 16, 5120, jnp.bfloat16
    lowered = jax.jit(s6_update_in_place, donate_argnums=0).lower(
        on((9, 64, n, c)), on((), jnp.int32), on((), jnp.int32),
        on((rows, c), bf16), on((rows, c)), on((n, c)), on((rows, n), bf16),
        on((rows, n), bf16))
    (body,) = _mosaic_bodies(lowered)
    assert f"@s6_update_r{rows}_n16_c5120" in body
    assert hashlib.sha256(body.encode()).hexdigest()[:16] == digest


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
            assert f"f32[{bucket},128,4096]" not in line \
                and f"f32[1,{bucket},128,4096]" not in line, line


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
